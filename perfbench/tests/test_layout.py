"""The cluster as the configuration lays it out: one DataNode in the
harness's process, or N DataNodes at replication N, each in a process of its
own with its own worker.  Rehearsed on the CPU with native workers, on a
scratch configuration kept out of ``BENCHMARK.json`` (``common.THREE_DN``)."""

import json
import os
import sys

import pytest

from common import (BENCH, THREE_DN, add_three_datanode_cell, checkout,
                    failing, rehearse)

CELL = f"{THREE_DN}.ingest"
# what the one-DataNode layout compared before N DataNodes were possible
BEFORE = ["ops_failed", "readback_bad", "logical_bytes_gap",
          "unique_chunks_gap", "unique_bytes_gap", "digests_missing",
          "sealed_decode_failures", "sealed_bytes_gap", "stored_bytes_gap",
          "worker_fallbacks", "degraded_writes", "breaker_open_total",
          "reduction_degraded", "client_block_retries",
          "blocks_not_on_worker", "parent_jax_backends", "device_not_tpu",
          "no_device_dispatch", "fault_planted"]
NEW = ["replicas_short", "mirror_failures"]


@pytest.fixture(scope="module")
def three_root(tmp_path_factory):
    return str(checkout(tmp_path_factory.mktemp("three"),
                        add_three_datanode_cell))


def _checks_note(rows):
    return [r for r in rows if r.get("phase") == "checks"][0]


def test_three_datanodes_hold_every_replica_to_the_reference(three_root):
    out, rows = rehearse(CELL, root=three_root, seed=2**31 + 37)
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert last["compared"]["device_not_tpu"]["value"] == 3   # one a worker
    assert last["device"]["chips_used"] == 3
    # set-up warms the workers whose DataNode led no pipeline in it
    warmed = [r for r in rows if r.get("phase") == "setup"][0]["warmed"]
    assert len(warmed) == 3 and not all(warmed)
    note = _checks_note(rows)
    per = note["per_datanode"]
    assert sorted(per) == ["dn-0", "dn-1", "dn-2"]
    for dn_id, values in per.items():
        assert set(values.values()) == {0}, (dn_id, values)
    # every index holds every block: three replicas of the reference
    assert [i["logical_bytes"] for i in note["index"]] == \
        [note["reference"]["logical_bytes"]] * 3
    assert [s["sealed"] > 0 for s in note["sealed"]] == [True] * 3
    # every range read back from each of the three locations
    rb = note["readback"]
    assert rb["reads"] > 0 and rb["reads"] % 3 == 0 and not rb["errors"]
    assert set(BEFORE + NEW) == set(last["compared"])
    # stored_pct reads per replica, as in the one-DataNode cells
    assert 15 < last["metrics"]["stored_pct"]["value"] < 25


@pytest.mark.parametrize("fault,names", [
    ("dn-truncate-sealed", {"sealed_decode_failures", "stored_bytes_gap"}),
    ("dn-drop-index-entry", {"digests_missing", "unique_chunks_gap"}),
])
def test_a_fault_on_one_datanode_fails_it_alone(three_root, fault, names):
    out, rows = rehearse(CELL, root=three_root, seed=2**31 + 41,
                         extra=["--fault", fault])
    last = rows[-1]
    assert last["correct"] is False and last["fault"] == fault
    assert names <= set(failing(last)), out.stderr[-3000:]
    per = _checks_note(rows)["per_datanode"]
    assert names <= {k for k, v in per["dn-2"].items() if v}
    assert set(per["dn-0"].values()) == set(per["dn-1"].values()) == {0}


def test_three_datanodes_traced(three_root):
    out, rows = rehearse(CELL, trace=1, root=three_root, seed=2**31 + 43)
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # the NameNode's share from the harness's partition, each DataNode's
    # from its own: averaged, so a share reads as one DataNode's
    assert m["nn.rpc_pct"] > 0 and m["dn.commit_pct"] > 0
    assert 0 < m["seal.thread_busy_pct"] <= 100
    assert 0 < m["dn.host_busy_pct"] <= 100
    assert m["dn.block_wall_ms"] > 0 and m["worker.reduce_ms_per_block"] > 0


def test_one_datanode_compares_as_before_and_two_more_at_zero():
    out, rows = rehearse("teragen-1dn.ingest", seed=2**31 + 47)
    last = rows[-1]
    assert list(last["compared"]) == BEFORE[:15] + NEW + BEFORE[15:]
    assert all(last["compared"][k]["value"] == 0 for k in NEW)
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert "chips_used" not in last["device"]
    assert list(_checks_note(rows)["per_datanode"]) == ["dn-0"]


def _cluster():
    sys.path.insert(0, BENCH)
    import cluster

    return cluster


def _config(**cl):
    with open(os.path.join(BENCH, "configs", "teragen-1dn.json")) as f:
        cfg = json.load(f)
    cfg["cluster"].update(cl)
    return cfg


@pytest.mark.parametrize("cl", [
    {"datanodes": 3, "workers": 3, "replication": 2, "chips": 4},
    {"datanodes": 3, "workers": 1, "replication": 3, "chips": 4},
    {"datanodes": 3, "workers": 3, "replication": 3, "chips": 1},
    {"datanodes": 1, "workers": 1, "replication": 1, "namenodes": 2},
], ids=["replication-below-datanodes", "shared-worker", "too-few-chips",
        "two-namenodes"])
def test_a_layout_it_does_not_run_is_refused_by_name(cl):
    with pytest.raises(SystemExit, match="replication"):
        _cluster().layout(_config(**cl))


def test_layouts_it_runs():
    cluster = _cluster()
    assert cluster.layout(_config()) == 1
    assert cluster.layout(_config(datanodes=3, workers=3, replication=3,
                                  chips=4)) == 3


def test_partitions_merge_per_datanode():
    cluster = _cluster()

    def part(scale, extra=None):
        return {"wall_s": 10.0, "hidden_wait_s": 1.0 * scale,
                "hideable_wait_s": 2.0, "overlap_efficiency": 0.5,
                "attributed_frac": 0.9,
                "classes": {"host_busy": 6.0 * scale, "idle": 1.0},
                "phases": dict({"recv": 2.0 * scale}, **(extra or {})),
                "inclusive": {"seal": {"count": 3, "wall_s": 1.5 * scale,
                                       "wall_max_s": 0.6 * scale,
                                       "cpu_s": 0.5}}}

    nn = part(1, {"nn_rpc": 0.7})
    nn["inclusive"]["nn_rpc"] = {"count": 9, "wall_s": 0.7,
                                 "wall_max_s": 0.1}
    got = cluster.merge_phases([part(1), part(2)], nn)
    assert got["datanodes"] == 2
    assert got["phases"] == {"recv": 3.0, "nn_rpc": 0.7}
    assert got["classes"]["host_busy"] == 9.0
    assert got["inclusive"]["seal"] == {"count": 6, "wall_s": 4.5,
                                        "wall_max_s": 1.2, "cpu_s": 1.0}
    assert got["inclusive"]["nn_rpc"]["count"] == 9
    sys.path.insert(0, BENCH)
    from readers import span_stat

    share = span_stat.read({"phases": got, "window_s": 10.0},
                           {"spans": ["seal"], "per": "window",
                            "scale": 100.0})
    assert share == pytest.approx(22.5)    # the mean DataNode's thread


def test_traces_merge_over_chips():
    sys.path.insert(0, BENCH)
    import run

    def tr(busy, secs):
        return {"chips": 1, "busy_s": busy, "device_events": 5,
                "programs": {"jit__prep_impl": {"seconds": secs,
                                                "count": 2}},
                "device_ops": [["a", secs]], "idle_gaps": [["host:x", 1.0]],
                "longest_gap_s": busy, "trace_bytes": 10}

    one = tr(1.0, 0.5)
    assert run.merge_traces([one], [10.0]) is one
    got = run.merge_traces([tr(1.0, 0.5), tr(3.0, 1.5)], [10.0, 10.0])
    assert got["busy_s"] == pytest.approx(2.0)       # mean idle share 80 %
    assert got["programs"]["jit__prep_impl"] == {"seconds": 2.0, "count": 4}
    assert got["device_ops"] == [["a", 2.0]]
    assert got["chips"] == 2 and got["longest_gap_s"] == 3.0
