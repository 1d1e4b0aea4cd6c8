"""PR 25's per-layer metrics reach the result line through the two channels
that were already open — the DataNode's phase ring and the numeric keys of
the worker's ``stats`` — with new layer files and one new reader only: one
traced rehearsal on a native worker prints every one a chip-less run can
read, and the manifest lists each with a layer file of its own.  (That no
file the benchmark already had is touched is the driver's check: a test of
it would pin a parent commit and break at the next PR that adds a metric.)"""

import json
import os

import pytest

from common import REPO, failing, rehearse

PHASE_SHARES = {"dn.packet_verify_pct", "dn.background_pct", "nn.rpc_pct",
                "hop.worker_send_pct", "seal.dn_pct"}
CHIPLESS = PHASE_SHARES | {"worker.ingest_wait_ms_per_block",
                           "worker.cpu_busy_pct", "seal.emit_ms_per_job"}
DEVICE_STAGES = {"worker.h2d_stage_ms_per_block",
                 "worker.prep_wait_ms_per_block",
                 "worker.select_ms_per_block",
                 "worker.sha_wait_ms_per_block", "seal.scan_wait_ms_per_job"}


def test_a_traced_rehearsal_prints_every_chipless_metric():
    out, rows = rehearse("teragen-1dn.ingest", trace=1, seed=2**31 + 25,
                         seconds=3)
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    got = last["metrics"]
    assert CHIPLESS <= set(got), sorted(CHIPLESS - set(got))
    # a native worker runs no device stage: left out, never printed as 0
    assert not DEVICE_STAGES & set(got)
    for name in CHIPLESS:
        assert got[name]["value"] >= 0.0
    for name in ("dn.packet_verify_pct", "hop.worker_send_pct",
                 "nn.rpc_pct", "worker.ingest_wait_ms_per_block",
                 "worker.cpu_busy_pct", "seal.emit_ms_per_job"):
        assert got[name]["value"] > 0.0, name
    shares = sum(got[n]["value"] for n in PHASE_SHARES | {
        "dn.recv_ack_pct", "dn.commit_pct", "dn.unattributed_pct",
        "hop.device_wait_pct"})
    assert shares <= 100.0 + 1e-6       # exclusive seconds of one window


def test_the_reader_returns_nothing_on_a_program_without_the_clock():
    """The parent's ``stats`` carry no stage key: ``stage_ratio`` leaves its
    metric out where ``stat_ratio`` would print a 0 nobody measured."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "perfbench"))
    from readers import stage_ratio, stat_ratio

    src = {"window_s": 10.0,
           "window": {"stats": {"blocks_reduced": 3, "reduce_s": 0.3}}}
    params = {"num": "ingest_wait_s", "den": "blocks_reduced",
              "scale": 1000.0}
    assert stage_ratio.read(src, params) is None
    assert stat_ratio.read(src, params) == 0.0
    src["window"]["stats"]["ingest_wait_s"] = 0.6
    assert stage_ratio.read(src, params) == pytest.approx(200.0)
    assert stage_ratio.read(src, dict(params, den="compress_jobs")) is None


def test_the_manifest_lists_every_metric_with_its_layer_file():
    """A superset check: later PRs append more ``per_layer`` entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert CHIPLESS | DEVICE_STAGES <= set(listed)
    readers = {"stage_ratio", "stat_ratio", "phase_share"}
    for name in CHIPLESS | DEVICE_STAGES:
        m = listed[name]
        assert m["moves"] == "write_mb_s" and m["better"] == "lower"
        assert {"teragen-1dn.ingest", "teragen-1dn.ingest-1w"} \
            <= set(m["workloads"])
        with open(os.path.join(REPO, "perfbench", "layers",
                               name + ".json")) as f:
            layer = json.load(f)
        assert layer["metric"] == name and layer["reader"] in readers
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "readers", layer["reader"] + ".py"))
