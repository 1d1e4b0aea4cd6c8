"""The deployment under test, as PR 22 proved it on the chip: NameNode and
DataNode in the harness's process (``MiniCluster``), the reduction worker as
the one child that owns the chip — here ``perfbench/worker_entry.py``, which
the DataNode is pointed at through ``reduction_overrides["worker_addr"]``.
Also the phase-clock sampler, and the counters the checks read.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Worker:
    """``worker_entry.py`` as a child; JSON lines over its stdin/stdout."""

    def __init__(self, backend: str, fault: str = ""):
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        argv = [sys.executable, os.path.join(HERE, "worker_entry.py"),
                "--backend", backend]
        if fault:
            argv += ["--fault", fault]
        self.proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True,
                                     env=env, cwd=ROOT)
        self._lock = threading.Lock()
        hello = self._read()
        if hello is None or "listening" not in hello:
            rc = self.proc.wait()
            raise RuntimeError(f"the reduction worker did not start (exit "
                               f"code {rc}); its stderr is above")
        self.addr = tuple(hello["listening"])
        self.backend = hello["backend"]
        self.device = hello["device"]

    def _read(self) -> dict | None:
        while True:
            line = self.proc.stdout.readline()
            if not line:
                return None
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    continue

    def ask(self, **req) -> dict:
        with self._lock:
            self.proc.stdin.write(json.dumps(req) + "\n")
            self.proc.stdin.flush()
            out = self._read()
        if out is None:
            raise RuntimeError(f"the worker died on {req.get('cmd')!r}")
        if not out.get("ok"):
            raise RuntimeError(f"worker {req.get('cmd')}: {out.get('error')}")
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.ask(cmd="quit")
            except (RuntimeError, OSError, ValueError):
                pass
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass


def start_cluster(config: dict, worker: Worker):
    from hdrf_tpu.testing.minicluster import MiniCluster

    cl = config["cluster"]
    if (cl["namenodes"], cl["datanodes"], cl["workers"]) != (1, 1, 1):
        raise SystemExit("this harness runs one NameNode, one DataNode and "
                         "one worker; a wider layout needs its own bring-up")
    overrides = dict(cl.get("reduction", {}))
    overrides["worker_addr"] = list(worker.addr)
    return MiniCluster(n_datanodes=1, replication=cl["replication"],
                       block_size=cl["block_size"],
                       container_size=cl["container_size"],
                       heartbeat_s=cl["heartbeat_interval_s"],
                       dead_node_s=cl["dead_node_interval_s"],
                       tpu_worker=False, backend="native",
                       reduction_overrides=overrides).start()


class PhaseSampler(threading.Thread):
    """Collects the DataNode's phase-clock spans through the window.

    The clock's ring holds 65 536 raw spans (``utils/profiler.py``) and a
    128 MiB block records several thousand, so one read at the end of a
    window of fifteen blocks finds a ring that has wrapped.  A span is
    recorded when it ends; this thread reads the ring every ``every``
    seconds, keeps each span once, and the whole window is partitioned once,
    after it has closed (``profile``)."""

    def __init__(self, t0: float, every: float = 1.0):
        super().__init__(name="perfbench-phase-sampler", daemon=True)
        self.t0, self.every = t0, every
        self.spans: dict[tuple, tuple] = {}
        self.halt = threading.Event()

    def _take(self) -> None:
        from hdrf_tpu.utils import profiler

        for sp in profiler.window_spans(self.t0, float("inf")):
            self.spans.setdefault((sp[0], sp[2], sp[3]), sp)

    def run(self) -> None:
        while not self.halt.wait(self.every):
            self._take()

    def profile(self, t1: float) -> dict:
        from hdrf_tpu.utils import profiler

        self.halt.set()
        self.join()
        self._take()
        return profiler.profile_spans(self.spans.values(), self.t0, t1)


def give_way_counters(dn) -> dict:
    """The counters that say the device path gave way to the host (copied
    from ``chip_smoke.give_way_counters``; the client's two are read in the
    client processes)."""
    from hdrf_tpu.utils import metrics

    c = {r: metrics.registry(r).snapshot()["counters"]
         for r in ("block_receiver", "datanode", "dedup", "resilience")}
    return {
        "worker_fallbacks": (c["block_receiver"].get("worker_fallbacks", 0)
                             + c["datanode"].get("worker_fallbacks", 0)
                             + c["dedup"].get("worker_fallbacks", 0)),
        "degraded_writes": c["block_receiver"].get("degraded_writes", 0),
        "breaker_open_total": c["resilience"].get("breaker_open_total", 0),
        "reduction_degraded": int(bool(dn.reduction_degraded)),
        "worker_reduces": c["block_receiver"].get("worker_reduces", 0),
    }


def parent_backends() -> list[str]:
    """JAX backends this process has initialised: none, if the chip is the
    worker's alone."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return [] if xb is None else sorted(getattr(xb, "_backends", {}))


def snapshot(dn) -> dict:
    """Worker and DataNode counters at one instant (window deltas are
    differences of two of these)."""
    rep = dn._worker.device_report()
    return {"t": time.time(), "stats": dn._worker.stats(),
            "lz4": rep["lz4"], "compile_s": rep["compile_s"],
            "dispatch_total": rep["ledger"].get("dispatch_total", 0),
            "cache_dir": rep["cache_dir"],
            "give_way": give_way_counters(dn), "index": dn.index.stats()}
