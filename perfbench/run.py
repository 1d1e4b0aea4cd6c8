#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never initialises JAX.  It builds the native library, starts the
reduction worker that owns the chip (``worker_entry.py``), the MiniCluster
(NameNode + DataNode, here) and the load-generator processes
(``loadgen.py``), runs set-up, releases the clients together, measures for
``--seconds`` and until the last acknowledgement **and**
``dn.containers.drain_seals()``, compares what the timed path stored and
returned with the plain reference, and prints one JSON line last on stdout.
With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (phase clock sampled, a few steady
seconds of the worker under ``jax.profiler``).  ``README.md`` has the rest.
"""

from __future__ import annotations

T_START = __import__("time").time()

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import checks  # noqa: E402 — perfbench's own modules, found through HERE;
import cluster  # noqa: E402   none of them imports the program or JAX
import layers_read  # noqa: E402
import loadgen  # noqa: E402
import manifest  # noqa: E402

MB = 1e6


def note(**kw) -> None:
    """Progress and readings, one JSON object per line on stdout (the result
    is the last line; everything before it is notes)."""
    print(json.dumps(kw, sort_keys=True, default=str), flush=True)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # Rehearsals, tests and controls only (README.md, "Rehearsal").  A run
    # with any of these is never correct.
    p.add_argument("--worker-backend", default="tpu",
                   help="'native' rehearses every phase without a chip")
    p.add_argument("--set", action="append", default=[], metavar="KEY=INT",
                   help="override a cluster size (rehearsal only)")
    p.add_argument("--fault", default="", help="plant a fault (faults.py)")
    args = p.parse_args(argv)
    if args.set and args.worker_backend == "tpu":
        p.error("--set changes the cell's sizes: rehearsal only "
                "(--worker-backend native)")
    return args


def build_native() -> None:
    src = os.path.join(ROOT, "hdrf_tpu", "native")
    if not os.path.isdir(src):
        raise SystemExit("perfbench runs from a checkout of the repository: "
                         f"{src} is not there")
    # -march=native and git-ignored: built on the machine that runs it, once,
    # before any child could race to.
    subprocess.run(["make", "-s", "-C", src], check=True)


def merged_params(config: dict, traffic: dict) -> dict:
    params = dict(traffic.get("params", {}))
    params.update({k: v for k, v in config.get("data", {}).items()
                   if k != "generator"})
    params["file_bytes"] = (config["cluster"]["block_size"]
                            * int(params.get("file_blocks", 1)))
    return params


def percentile(values: list, q: float) -> float:
    vs = sorted(values)
    if not vs:
        return float("nan")
    k = min(int(round(q * (len(vs) - 1))), len(vs) - 1)
    return vs[k]


def end_to_end(ops: list, window_s: float, setup_s: float,
               stored: dict) -> dict:
    """Every end-to-end metric this run can report, by name and unit; the
    result line keeps those ``BENCHMARK.json`` lists for the cell."""
    out = {"setup_s": (setup_s, "s")}
    writes = [op for op in ops if op["kind"] == "write" and op["ok"]]
    reads = [op for op in ops if op["kind"] == "read" and op["ok"]]
    if writes:
        out["write_mb_s"] = (sum(op["bytes"] for op in writes) / MB
                             / window_s, "MB/s")
        if stored["unique_chunk_bytes"]:
            out["stored_pct"] = (100.0 * stored["physical_bytes"]
                                 / stored["unique_chunk_bytes"], "%")
    if reads:
        out["read_mb_s"] = (sum(op["bytes"] for op in reads) / MB / window_s,
                            "MB/s")
        out["read_p95_ms"] = (percentile(
            [(op["t1"] - op["t0"]) * 1e3 for op in reads], 0.95), "ms")
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)
            if isinstance(after.get(k, 0), (int, float))}


class Tracer(threading.Thread):
    """Starts ``jax.profiler`` in the worker a third of the way into the
    window and stops it ``length`` seconds later (a third of the window, 15 s
    at the most: four writers in step hand the device its blocks in one
    burst per round of some 9 s, and the trace has to hold one); worker and
    DataNode counters are read at both ends, so bytes and device time cover
    the same seconds.  The trace is reduced after the window."""

    def __init__(self, worker, dn, t_release: float, seconds: float,
                 trace_dir: str):
        super().__init__(name="perfbench-tracer", daemon=True)
        self.worker, self.dn = worker, dn
        self.at = t_release + 0.35 * seconds
        self.length = min(15.0, seconds / 3.0)
        self.dir = trace_dir
        self.result: dict | None = None
        self.error: str | None = None

    def run(self) -> None:
        try:
            time.sleep(max(self.at - time.time(), 0.0))
            s0 = cluster.snapshot(self.dn)
            t0 = self.worker.ask(cmd="trace_start", dir=self.dir)["t_start"]
            time.sleep(self.length)
            t1 = self.worker.ask(cmd="trace_stop")["t_stop"]
            s1 = cluster.snapshot(self.dn)
            self.result = {"t0": t0, "t1": t1, "window_s": t1 - t0,
                           "stats": delta(s1["stats"], s0["stats"]),
                           "lz4": delta(s1["lz4"], s0["lz4"])}
        except Exception as e:  # noqa: BLE001 — reported with the result
            self.error = f"{type(e).__name__}: {e}"


def main(argv=None) -> int:
    args = parse(argv)
    cell = manifest.cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    for kv in args.set:
        key, val = kv.split("=", 1)
        config["cluster"][key] = int(val)
    if cell["workload"]["chips"] != config["cluster"]["chips"]:
        raise SystemExit("the cell's chips differ from its configuration's")
    params = merged_params(config, traffic)
    build_native()
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")

    n_clients = int(traffic["clients"])
    specs = [{"seed": args.seed, "idx": i, "clients": n_clients,
              "params": params, "config": config, "repo": ROOT,
              "driver": traffic["driver"],
              "generator": config["data"]["generator"],
              "fault": args.fault} for i in range(n_clients)]
    clients = worker = mc = None
    trace_dir = None
    times = {"t_start": T_START}
    try:
        clients = loadgen.Clients(specs)           # they make their data now
        worker = cluster.Worker(args.worker_backend, args.fault)
        times["worker_up_s"] = time.time() - T_START
        if worker.backend == "tpu":
            peaks = manifest.peaks(worker.device["kind"])
            if worker.device["count"] < cell["workload"]["chips"]:
                raise SystemExit(f"the cell asks for "
                                 f"{cell['workload']['chips']} chips, JAX "
                                 f"reports {worker.device['count']}")
        else:
            peaks = None
        mc = cluster.start_cluster(config, worker)
        dn = mc.datanodes[0]
        times["cluster_up_s"] = time.time() - T_START
        before = cluster.snapshot(dn)
        ready = clients.wait_ready()
        times["clients_ready_s"] = time.time() - T_START
        clients.call("connect", list(mc.nn_addrs()[0]))
        setup_ops = [op for ops in clients.call("setup") for op in ops]
        dn.containers.drain_seals()
        times["setup_ops_s"] = time.time() - T_START
        warm = cluster.snapshot(dn)
        note(phase="setup", times=times, worker=worker.device,
             backend=worker.backend, prepare_s=[r["prepare_s"] for r in ready],
             setup_ops=len(setup_ops), compile_s=warm["compile_s"],
             cache_dir=warm["cache_dir"], lz4=warm["lz4"])

        # ---------------------------------------------------- the window
        sampler = tracer = None
        t_release = time.time() + 0.25
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            sampler = cluster.PhaseSampler(t_release)
            sampler.start()
            if worker.backend == "tpu":
                tracer = Tracer(worker, dn, t_release, args.seconds,
                                trace_dir)
                tracer.start()
        setup_s = t_release - T_START
        runs = clients.call("run", (t_release, args.seconds),
                            timeout=args.seconds + 600)
        dn.containers.drain_seals()
        t_end = time.time()
        window_s = t_end - t_release
        after = cluster.snapshot(dn)
        phases = sampler.profile(t_end) if sampler else None
        if tracer is not None:
            tracer.join()
        ops = [op for r in runs for op in r["ops"]]
        note(phase="window", window_s=window_s, ops=len(ops),
             op_s=[[round(op["t1"] - op["t0"], 3) for op in r["ops"]]
                   for r in runs] if len(ops) <= 64 else None,
             last_ack_s=max(op["t1"] for op in ops) - t_release,
             late_s=[r["late_s"] for r in runs],
             client_cpu_s=[r["cpu_s"] for r in runs])

        # ------------------------------------ after it: readings, then checks
        memory = worker.ask(cmd="memory")["memory"]
        trace = None
        if tracer is not None and tracer.result is not None:
            trace = dict(tracer.result)
            trace.update(worker.ask(cmd="trace_reduce",
                                    window_s=trace["window_s"])["trace"])
            note(phase="trace", **{k: trace[k] for k in (
                "window_s", "busy_s", "device_events", "programs",
                "longest_gap_s", "trace_bytes", "stats", "lz4")})
        dn.containers.flush_open()                  # the open tail seals too
        stored = {"physical_bytes": dn.containers.physical_bytes(),
                  "unique_chunk_bytes": dn.index.stats()["unique_chunk_bytes"]}
        final = cluster.snapshot(dn)
        client_checks = clients.call("check")
        chk, notes = checks.compare(
            dn, client_checks, setup_ops + ops, before, final,
            [r["counters"] for r in runs], worker, args.fault,
            cluster.parent_backends(), config["cluster"]["block_size"],
            stored["physical_bytes"])
        if args.trace and tracer is not None and tracer.error:
            chk["trace_failed"] = [1, 0]
            notes["trace_error"] = tracer.error
        note(phase="checks", **notes)
    finally:
        if clients is not None:
            clients.stop()
        if mc is not None:
            mc.stop()
        if worker is not None:
            worker.stop()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ------------------------------------------------------------ the result
    window_delta = {"stats": delta(after["stats"], warm["stats"]),
                    "lz4": delta(after["lz4"], warm["lz4"]),
                    "compile_s": sum(after["compile_s"].values())
                    - sum(warm["compile_s"].values())}
    if args.trace:
        sources = {"window_s": window_s, "clients": runs, "phases": phases,
                   "window": window_delta, "trace": trace, "peaks": peaks,
                   "config": config, "ops": ops}
        metrics = layers_read.read_all(cell["per_layer"], sources)
    else:
        have = end_to_end(ops, window_s, setup_s, stored)
        metrics = {m["name"]: {"value": have[m["name"]][0],
                               "unit": have[m["name"]][1]}
                   for m in cell["end_to_end"] if m["name"] in have}
    device = {"platform": worker.device.get("platform"),
              "kind": worker.device.get("kind"),
              "count": worker.device.get("count"),
              "memory_peak_bytes": memory.get("peak_bytes", 0)}
    result = {"correct": checks.verdict(chk),
              "attempted": len(ops),
              "failed": sum(1 for op in ops if not op["ok"]),
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    reads = [op for op in ops if op["kind"] == "read" and op["ok"]]
    result["workload"] = args.workload
    result["seed"] = args.seed
    result["window_s"] = window_s
    if reads:
        result["read_samples"] = len(reads)
    if args.fault:
        result["fault"] = args.fault
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in chk.items()}
    for k, (v, lim) in chk.items():
        print(f"compared {k}: {v} (limit {lim})"
              + ("" if v <= lim else "  <-- FAILS"), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
