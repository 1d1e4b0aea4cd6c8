"""Shared by the tests: run ``run.py`` at a tiny size on the CPU."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
TINY = ["--set", "block_size=2097152", "--set", "container_size=1048576"]


def run(argv, root=REPO, timeout=600):
    """Returns (completed process, stdout lines that parse as JSON)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    rows = []
    for ln in out.stdout.splitlines():
        if ln.startswith("{"):
            try:
                rows.append(json.loads(ln))
            except ValueError:
                pass
    return out, rows


def rehearse(workload, trace=0, seed=7, seconds=2, extra=(), root=REPO):
    return run(["--workload", workload, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(trace), "--worker-backend",
                "native", *TINY, *extra], root=root)


def failing(result):
    return sorted(k for k, c in result["compared"].items()
                  if c["value"] > c["limit"])


def checkout(tmp_path, mutate):
    """A throw-away checkout: this ``perfbench`` copied, the program linked,
    and ``BENCHMARK.json`` as ``mutate(bench)`` leaves it."""
    import shutil

    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "hdrf_tpu"), root / "hdrf_tpu")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mutate(bench, root / "perfbench")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def add_shelved_cells(bench, _pb=None):
    """The entries that would add the cell whose files are in place but which
    ``BENCHMARK.json`` does not list (``PERF.md`` section 7 says why): the
    positional-read cell without a writer, with the two end-to-end read
    metrics (listing the read cell that is there too) and the read window's
    per-layer metrics.  A cell, a configuration or a metric that
    ``BENCHMARK.json`` lists already is extended, never listed twice."""
    name = "teragen-1dn.pread"
    reads = [name, "teragen-1dn.pread-ingest"]
    bench["workloads"].append({
        "name": name, "config": "teragen-1dn", "traffic": "pread",
        "chips": 1, "why": "test"})
    for metric, unit in (("read_mb_s", "MB/s"), ("read_p95_ms", "ms")):
        bench["end_to_end"].append({
            "name": metric, "unit": unit, "better": "higher", "bound": 0.25,
            "source": "host_clock", "workloads": reads})
    for metric in ("client.busy_pct.read", "device.idle_pct.read"):
        bench["per_layer"].append({
            "name": metric, "unit": "%", "better": "lower",
            "source": "host_clock", "layer": "test", "moves": "read_mb_s",
            "workloads": reads})
    for m in bench["per_layer"]:
        if m["name"] in ("dn.decode_pct", "dn.net_send_pct"):
            m["workloads"].append(name)


THREE_DN = "teragen-3dn-r3"


def add_three_datanode_cell(bench, pb):
    """A scratch configuration kept out of the tree: ``teragen-1dn``'s groups
    with three DataNodes at replication 3 on four chips (BASELINE config 5's
    layout), and its cell ``teragen-3dn-r3.ingest`` under ``traffic/ingest``,
    listed by every write metric."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "teragen-1dn.json")))
    cfg["name"] = THREE_DN
    cfg["cluster"].update(datanodes=3, workers=3, replication=3, chips=4)
    (pb / "configs" / f"{THREE_DN}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": THREE_DN, "source": "a scratch run",
                             "file": f"perfbench/configs/{THREE_DN}.json",
                             "reduced": ["data_bytes"], "why": "scratch"})
    cell = f"{THREE_DN}.ingest"
    bench["workloads"].append({"name": cell, "config": THREE_DN,
                               "traffic": "ingest", "chips": 4,
                               "why": "scratch"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "teragen-1dn.ingest" in m["workloads"]:
            m["workloads"].append(cell)
