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
import faults  # noqa: E402
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
    """Starts ``jax.profiler`` in every worker a third of the way into the
    window and stops it ``length`` seconds later (a third of the window, 15 s
    at the most: four writers in step hand the device its blocks in one
    burst per round of some 9 s, and the trace has to hold one); worker and
    DataNode counters are read at both ends, so bytes and device time cover
    the same seconds.  The traces are reduced after the window."""

    def __init__(self, workers, nodes, t_release: float, seconds: float,
                 trace_dirs: list):
        super().__init__(name="perfbench-tracer", daemon=True)
        self.workers, self.nodes = workers, nodes
        self.at = t_release + 0.35 * seconds
        self.length = min(15.0, seconds / 3.0)
        self.dirs = trace_dirs
        self.result: dict | None = None
        self.error: str | None = None

    def run(self) -> None:
        try:
            time.sleep(max(self.at - time.time(), 0.0))
            s0 = cluster.each(self.nodes, lambda n: n.ask(cmd="snapshot"))
            t0 = [w.ask(cmd="trace_start", dir=d)["t_start"]
                  for w, d in zip(self.workers, self.dirs)]
            time.sleep(self.length)
            t1 = [w.ask(cmd="trace_stop")["t_stop"] for w in self.workers]
            s1 = cluster.each(self.nodes, lambda n: n.ask(cmd="snapshot"))
            self.result = {"t0": t0[0], "t1": t1[0],
                           "window_s": t1[0] - t0[0],
                           "windows_s": [b - a for a, b in zip(t0, t1)],
                           "stats": summed(s1, s0, "stats"),
                           "lz4": summed(s1, s0, "lz4")}
        except Exception as e:  # noqa: BLE001 — reported with the result
            self.error = f"{type(e).__name__}: {e}"


def summed(after: list, before: list, key: str) -> dict:
    """Window deltas of one group of counters, summed over the DataNodes
    (each DataNode's worker is its own)."""
    out: dict = {}
    for a, b in zip(after, before):
        for k, v in delta(a["snapshot"][key], b["snapshot"][key]).items():
            out[k] = out.get(k, 0) + v
    return out


def merge_traces(traces: list, windows: list) -> dict:
    """One reduced trace from every worker's, over the same seconds: busy
    seconds such that ``1 - busy / window`` is the mean of the chips' idle
    shares, programs' seconds and counts summed (a roofline is the summed
    bytes over the summed device time), operations and gaps summed by name
    (the ten largest kept), the longest gap the longest of any chip."""
    if len(traces) == 1:
        return traces[0]
    window = sum(windows) / len(windows)
    programs: dict = {}
    for t in traces:
        for name, p in t["programs"].items():
            acc = programs.setdefault(name, {"seconds": 0.0, "count": 0})
            acc["seconds"] += p["seconds"]
            acc["count"] += p["count"]

    def top(key: str) -> list:
        acc: dict = {}
        for t in traces:
            for name, secs in t[key]:
                acc[name] = acc.get(name, 0.0) + secs
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:10]]

    return {"chips": sum(t["chips"] for t in traces),
            "busy_s": window * sum(t["busy_s"] / w for t, w in
                                   zip(traces, windows)) / len(traces),
            "busy_s_by_chip": [t["busy_s"] for t in traces],
            "device_events": sum(t["device_events"] for t in traces),
            "programs": programs, "device_ops": top("device_ops"),
            "idle_gaps": top("idle_gaps"),
            "longest_gap_s": max(t["longest_gap_s"] for t in traces),
            "trace_bytes": sum(t["trace_bytes"] for t in traces),
            "window_s": window}


def main(argv=None) -> int:
    args = parse(argv)
    cell = manifest.cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    for kv in args.set:
        key, val = kv.split("=", 1)
        config["cluster"][key] = int(val)
    if cell["workload"]["chips"] != config["cluster"]["chips"]:
        raise SystemExit("the cell's chips differ from its configuration's")
    n_dn = cluster.layout(config)
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
    clients = mc = None
    workers, nodes, trace_dirs = [], [], []
    times = {"t_start": T_START}
    try:
        clients = loadgen.Clients(specs)           # they make their data now
        workers = cluster.start_workers(args.worker_backend, args.fault, n_dn)
        worker = workers[0]
        times["worker_up_s"] = time.time() - T_START
        if worker.backend == "tpu":
            peaks = manifest.peaks(worker.device["kind"])
            chips = cluster.device_count(workers)
            if chips < cell["workload"]["chips"]:
                raise SystemExit(f"the cell asks for "
                                 f"{cell['workload']['chips']} chips, JAX "
                                 f"reports {chips}")
        else:
            peaks, chips = None, worker.device["count"]
        mc, nodes = cluster.start_cluster(config, workers)
        times["cluster_up_s"] = time.time() - T_START
        before = cluster.each(nodes, lambda n: n.ask(cmd="snapshot"))
        ready = clients.wait_ready()
        times["clients_ready_s"] = time.time() - T_START
        clients.call("connect", list(mc.nn_addrs()[0]))
        setup_ops = [op for ops in clients.call("setup") for op in ops]
        warmed = [] if n_dn == 1 else cluster.each(nodes, lambda n: n.ask(
            cmd="warm_reduce", nbytes=config["cluster"]["block_size"],
            seed=args.seed)["warmed"])
        cluster.each(nodes, lambda n: n.ask(cmd="drain_seals"))
        times["setup_ops_s"] = time.time() - T_START
        warm = cluster.each(nodes, lambda n: n.ask(cmd="snapshot"))
        note(phase="setup", times=times,
             worker=[w.device for w in workers] if n_dn > 1
             else worker.device, backend=worker.backend,
             prepare_s=[r["prepare_s"] for r in ready],
             setup_ops=len(setup_ops), warmed=warmed,
             compile_s=[w["snapshot"]["compile_s"] for w in warm]
             if n_dn > 1 else warm[0]["snapshot"]["compile_s"],
             cache_dir=warm[0]["snapshot"]["cache_dir"],
             lz4=[w["snapshot"]["lz4"] for w in warm] if n_dn > 1
             else warm[0]["snapshot"]["lz4"])

        # ---------------------------------------------------- the window
        sampler = tracer = None
        remote = [n for n in nodes if n.remote]
        t_release = time.time() + 0.25
        if args.trace:
            sampler = cluster.PhaseSampler(t_release)
            sampler.start()
            cluster.each(remote, lambda n: n.ask(cmd="phases_start",
                                                 t0=t_release))
            if worker.backend == "tpu":
                trace_dirs = [tempfile.mkdtemp(prefix="perfbench-trace-")
                              for _ in workers]
                tracer = Tracer(workers, nodes, t_release, args.seconds,
                                trace_dirs)
                tracer.start()
        setup_s = t_release - T_START
        runs = clients.call("run", (t_release, args.seconds),
                            timeout=args.seconds + 600)
        cluster.each(nodes, lambda n: n.ask(cmd="drain_seals"))
        t_end = time.time()
        window_s = t_end - t_release
        after = cluster.each(nodes, lambda n: n.ask(cmd="snapshot"))
        phases = sampler.profile(t_end) if sampler else None
        if sampler and remote:
            parts = cluster.each(remote, lambda n: n.ask(
                cmd="phases", t1=t_end)["phases"])
            phases = cluster.merge_phases(parts, phases)
            note(phase="phases", exclusive_s={
                n.dn_id: p["phases"] for n, p in zip(remote, parts)})
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
        memory = [w.ask(cmd="memory")["memory"] for w in workers]
        note(phase="device", chips=[w.device.get("chip") for w in workers],
             memory_peak_bytes=[m.get("peak_bytes") for m in memory])
        trace = None
        if tracer is not None and tracer.result is not None:
            trace = dict(tracer.result)
            trace.update(merge_traces(
                [w.ask(cmd="trace_reduce", window_s=win)["trace"]
                 for w, win in zip(workers, trace["windows_s"])],
                trace["windows_s"]))
            note(phase="trace", **{k: trace[k] for k in (
                "window_s", "busy_s", "device_events", "programs",
                "longest_gap_s", "trace_bytes", "stats", "lz4")})
        cluster.each(nodes, lambda n: n.ask(cmd="flush_open"))  # open tails
        if args.fault in faults.DATANODE_FAULTS:
            nodes[-1].ask(cmd="plant", fault=args.fault)
        held = cluster.each(nodes, lambda n: n.ask(cmd="stored"))
        stored = {"physical_bytes": sum(h["physical_bytes"] for h in held),
                  "unique_chunk_bytes": sum(h["index"]["unique_chunk_bytes"]
                                            for h in held)}
        final = cluster.each(nodes, lambda n: n.ask(cmd="snapshot"))
        client_checks = clients.call("check")
        table: dict = {}
        for c in client_checks:
            for d, ln in c["table"].items():
                table.setdefault(d, ln)
        per_node = cluster.each(nodes, lambda n: dict(
            n.ask(cmd="decode_sealed"), missing=n.missing(table)))
        for i, node in enumerate(nodes):
            per_node[i].update(
                dn_id=node.dn_id, before=before[i]["snapshot"],
                after=final[i]["snapshot"], backend=workers[i].backend,
                physical_bytes=held[i]["physical_bytes"])
            if not node.remote:     # its process is the harness's
                per_node[i]["backends"] = None
        chk, notes = checks.compare(
            per_node, client_checks, setup_ops + ops,
            [r["counters"] for r in runs], workers, args.fault,
            cluster.parent_backends(), config["cluster"]["block_size"])
        if args.trace and tracer is not None and tracer.error:
            chk["trace_failed"] = [1, 0]
            notes["trace_error"] = tracer.error
        note(phase="checks", **notes)
    finally:
        if clients is not None:
            clients.stop()
        for node in nodes:
            node.stop()
        if mc is not None:
            mc.stop()
        for w in workers:
            w.stop()
        for d in trace_dirs:
            shutil.rmtree(d, ignore_errors=True)

    # ------------------------------------------------------------ the result
    window_delta = {"stats": summed(after, warm, "stats"),
                    "lz4": summed(after, warm, "lz4"),
                    "compile_s": sum(sum(a["snapshot"]["compile_s"].values())
                                     - sum(w["snapshot"]["compile_s"].values())
                                     for a, w in zip(after, warm))}
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
              "count": chips,
              "memory_peak_bytes": max(m.get("peak_bytes", 0)
                                       for m in memory)}
    if n_dn > 1:
        device["chips_used"] = len(workers)
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
