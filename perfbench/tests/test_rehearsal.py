"""CPU rehearsal of ``run.py`` for every cell at a tiny size with a native
worker: every phase runs (clients, set-up, window, read-back, reference,
sealed containers, counters), then the run ends ``correct: false`` for want
of a chip — and for nothing else.  There is no CPU success mode."""

import json
import os

import pytest

from common import BENCH, REPO, failing, rehearse, run  # noqa: F401

CELLS = [w["name"] for w in json.load(
    open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]


def _manifest():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_every_phase_runs_then_only_the_chip_is_missing(workload, trace):
    out, rows = rehearse(workload, trace=trace, seed=2**31 + 11)
    assert out.returncode != 0
    last = rows[-1]
    assert last["correct"] is False
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] is None
    phases = {r["phase"] for r in rows[:-1] if "phase" in r}
    assert {"setup", "window", "checks"} <= phases
    bench = _manifest()
    kind = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])}
    assert set(last["metrics"]) <= mine
    if trace == 0:
        assert set(last["metrics"]) == mine
        assert all(v["value"] > 0 for v in last["metrics"].values())
    else:
        # no chip, so nothing read from a trace is printed under any name
        traced = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"}
        assert not traced & set(last["metrics"])
        assert "busy_s" not in last["device"]
    # the compared numbers are the last lines of stderr, each with its limit
    tail = out.stderr.strip().splitlines()[-len(last["compared"]):]
    assert all(ln.startswith("compared ") and "(limit " in ln for ln in tail)
    assert list(last)[-1] == "compared"


def test_same_seed_same_inputs():
    import sys
    sys.path.insert(0, BENCH)
    from generators import teragen, versions

    p = {"file_bytes": 1 << 20, "median_file_bytes": 6144, "sigma": 1.4,
         "edit_share": 0.03, "churn_share": 0.005, "edit_lines_max": 8}
    big = 2**31 + 12345
    for mod in (teragen, versions):
        a = [mod.Source(p, big, 1).file(k).tobytes() for k in range(3)]
        b = [mod.Source(p, big, 1).file(k).tobytes() for k in range(3)]
        c = mod.Source(p, big + 1, 1).file(0).tobytes()
        assert a == b and a[0] != c and len(set(a)) == 3
        assert all(len(x) == p["file_bytes"] for x in a)


def test_no_accelerator_no_result():
    out, rows = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert out.returncode != 0
    assert not any("correct" in r for r in rows)
    assert "refusing to start" in out.stderr


def test_sizes_cannot_change_on_the_chip_path():
    out, rows = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0", "--set", "block_size=1048576"])
    assert out.returncode != 0 and not rows


def test_alone_in_a_directory_it_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out, rows = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"], root=str(tmp_path))
    assert out.returncode != 0
    assert not any("correct" in r for r in rows)
