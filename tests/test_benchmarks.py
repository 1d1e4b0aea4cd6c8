"""The in-tree perf harnesses run end-to-end at tiny sizes (the reference
keeps NNThroughputBenchmark etc. in the test tree; results are printed JSON,
not asserted)."""

import json
import io
from contextlib import redirect_stdout

from hdrf_tpu import benchmarks


def run(argv) -> list[dict]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert benchmarks.main(argv) == 0
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def test_nn_metadata_storm_one_json_line():
    """`benchmarks nn` contract (ISSUE 18 acceptance): EXACTLY one JSON
    line carrying the contention observatory's storm verdict —
    rpc_p99_ms, lock_saturation, the per-method lock-share curve and the
    attribution fraction.  Tiny storm: shape and sanity, not the bar."""
    out = run(["nn", "--ops", "60", "--clients", "3", "--meta-per-op", "2"])
    assert len(out) == 1
    (o,) = out
    assert o["bench"] == "nn_metadata_storm"
    assert o["clients"] == 3 and o["errors"] == 0
    assert o["ops_per_s"] > 0 and o["rpc_calls"] > 0
    assert o["rpc_p99_ms"] > 0
    assert 0.0 <= o["lock_saturation"] <= 1.0
    assert o["lock_wait_p99_us"] >= 0.0
    assert o["top_method"] in o["lock_share"]
    assert all(0.0 <= v <= 1.0 for v in o["lock_share"].values())
    assert o["attributed_frac"] >= 0.95


def test_dfs_throughput():
    out = run(["dfs", "--mb", "2", "--datanodes", "2", "--replication", "1",
               "--schemes", "direct,dedup_lz4"])
    assert len(out) == 2 and all(o["write_MBps"] > 0 for o in out)


def test_ec_throughput():
    # PR 8 contract: the ec harness prints ONE JSON line — the paired
    # encode/intact/degraded slope report, oracle-pinned before timing
    out = run(["ec", "--mb", "3", "--policy", "rs-3-2-4k", "--inner", "2"])
    assert len(out) == 1
    (o,) = out
    assert o["parity_oracle_ok"] is True
    assert o["k"] == 3 and o["m"] == 2
    assert o["encode_MBps"] > 0 and o["degraded_read_MBps"] > 0


def test_ec_repair_ab_one_json_line():
    # PR 16 contract: the paired repair harness prints ONE JSON line —
    # coded partial-sum repair vs the classic full gather, every erasure
    # pattern oracle-pinned before timing, wire ratio well below k
    out = run(["ec", "--repair-ab", "--mb", "2", "--policy", "rs-3-2-4k",
               "--inner", "2", "--dns", "4"])
    assert len(out) == 1
    (o,) = out
    assert o["op"].startswith("ec repair A/B")
    assert o["parity_oracle_ok"] is True
    assert o["patterns_pinned"] > 0
    assert o["repair_wire_ratio_coded"] < o["repair_wire_ratio_full"]
    assert o["repair_wire_ratio_coded"] <= 1.0 + 1e-6
    assert abs(o["repair_wire_ratio_full"] - o["k"]) < 1e-6


def test_reduction_throughput():
    out = run(["reduction", "--mb", "4", "--backend", "native"])
    assert out[0]["chunks"] > 0


def test_cdc_harness_one_json_line():
    """`benchmarks cdc` contract: EXACTLY one JSON line carrying the
    fused-vs-XLA slope A/B and the per-block readback byte ledger (the
    ISSUE 4 acceptance shape).  Tiny corpus; the fused kernel runs in the
    Pallas interpreter on the CPU mesh."""
    out = run(["cdc", "--mb", "1", "--inner", "2", "--repeats", "1"])
    assert len(out) == 1
    (o,) = out
    assert o["op"].startswith("cdc_prep")
    assert o["interpret"] is True  # no chip on the test mesh
    assert o["fused_ms_per_block"] > 0 and o["xla_ms_per_block"] > 0
    assert o["cand_d2h_bytes_per_block_xla"] > \
        o["cut_table_d2h_bytes_per_block_fused"]
    assert o["serial_awaited_boundaries"] == {"xla": 2, "fused": 1}


def test_multichip_harness_one_json_line():
    """`benchmarks multichip` contract: EXACTLY one JSON line — the
    1/2/4/8-device service-rate curve, pinned bit-identical to the native
    oracle before timing, with device-ledger evidence that every mesh
    step was ONE dispatch (the ISSUE 9 acceptance shape).  Tiny corpus,
    one repeat — this asserts the protocol and line shape, not the
    scaling bar (PERF_NOTES round 13 carries the measured curve)."""
    out = run(["multichip", "--blocks", "16", "--repeats", "1"])
    assert len(out) == 1
    (o,) = out
    assert o["op"].startswith("multichip")
    assert o["oracle_ok"] is True
    assert o["one_dispatch_per_step"] is True
    assert set(o["MBps"]) == {"1", "2", "4", "8"}
    assert all(v > 0 for v in o["MBps"].values())
    assert o["ratio_8v1"] > 0
    assert o["steps"] == o["step_dispatches"]


def test_sort_harness():
    out = run(["sort", "--tiles", "1", "--entries", "2048", "--inner", "2",
               "--repeats", "1"])
    ops = {o["op"] for o in out}
    # CPU mesh: only the XLA path times; the readback ledger always prints
    assert "match_deltas [xla]" in ops and "sort_rows [xla]" in ops
    (ledger,) = [o for o in out if o["op"] == "record readback"]
    assert ledger["reduction_pct"] >= 25.0  # the ISSUE acceptance bar
