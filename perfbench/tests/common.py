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
    """The entries that would add the three cells whose files are in place
    but which ``BENCHMARK.json`` does not list (``PERF.md`` section 7 says
    why): the positional-read cell, the same with a writer beside the
    readers, and the versioned-tree ingest cell."""
    reads = ["teragen-1dn.pread", "teragen-1dn.pread-ingest"]
    for name in reads:
        bench["workloads"].append({
            "name": name, "config": "teragen-1dn",
            "traffic": name.split(".", 1)[1], "chips": 1, "why": "test"})
    for metric, unit in (("read_mb_s", "MB/s"), ("read_p95_ms", "ms")):
        bench["end_to_end"].append({
            "name": metric, "unit": unit, "better": "higher", "bound": 0.25,
            "source": "host_clock", "workloads": reads})
    for metric in ("client.busy_pct.read", "device.idle_pct.read",
                   "dn.decode_pct", "dn.net_send_pct"):
        bench["per_layer"].append({
            "name": metric, "unit": "%", "better": "lower",
            "source": "host_clock", "layer": "test", "moves": "read_mb_s",
            "workloads": reads})
    with open(os.path.join(BENCH, "configs", "versions-dedup.json")) as f:
        source = json.load(f)["source"]
    bench["configs"].append({"name": "versions-dedup", "source": source,
                             "file": "perfbench/configs/versions-dedup.json",
                             "reduced": ["data_bytes", "index_entries"],
                             "why": "test"})
    name = "versions-dedup.ingest"
    bench["workloads"].append({"name": name, "config": "versions-dedup",
                               "traffic": "ingest", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("write_mb_s", "stored_pct") or (
                m.get("moves") == "write_mb_s"
                and m["name"] != "seal.device_emitted_pct"):
            m["workloads"].append(name)
