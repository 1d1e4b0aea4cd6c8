"""The deployment under test, laid out as the configuration's ``cluster``
group says.

- **One DataNode** (``namenodes``, ``datanodes``, ``workers`` = 1, 1, 1), as
  PR 22 proved it on the chip: NameNode and DataNode in the harness's process
  (``MiniCluster``), the reduction worker as the one child that owns the chip
  — ``perfbench/worker_entry.py``, which the DataNode is pointed at through
  ``reduction_overrides["worker_addr"]``.
- **N DataNodes at r=N** (``datanodes`` = ``workers`` = ``replication`` = N,
  ``chips`` >= N): the NameNode stays in the harness's process; each DataNode
  is a process of its own (``perfbench/datanode_entry.py``) with its own
  worker, and worker i sees chip i alone.  Any other layout is refused by
  name until a cell needs it.

Either way the harness holds a list of nodes with one interface
(``LocalNode`` / ``RemoteNode``), so run.py and checks.py read one DataNode
or N the same way.  Also the phase-clock sampler, and the counters the checks
read.
"""

from __future__ import annotations

import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Child:
    """A perfbench process of its own; JSON lines over its stdin/stdout.  The
    first line it prints is its hello."""

    what = "child"

    def __init__(self, argv: list, env: dict | None = None):
        env = dict(os.environ if env is None else env)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen([sys.executable, *argv],
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True,
                                     env=env, cwd=ROOT)
        self._lock = threading.Lock()

    def hello(self, key: str) -> dict:
        hello = self._read()
        if hello is None or key not in hello:
            rc = self.proc.wait()
            raise RuntimeError(f"the {self.what} did not start (exit code "
                               f"{rc}); its stderr is above")
        return hello

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
            raise RuntimeError(f"the {self.what} died on {req.get('cmd')!r}")
        if not out.get("ok"):
            raise RuntimeError(f"{self.what} {req.get('cmd')}: "
                               f"{out.get('error')}")
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


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def chip_env(chip: int) -> dict:
    """libtpu's environment for a process that sees chip ``chip`` alone of
    its host (one process a chip, none forming a slice with another; the
    names as JAX's own multi-process launcher sets them,
    ``jax/_src/test_multiprocess.py``).  Each process keeps its own runtime
    port; the lock file libtpu takes for the whole host is waived, since no
    two of these processes open the same chip (the harness refuses a
    layout in which two workers report one chip)."""
    port = _free_port()
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


class Worker(Child):
    """``worker_entry.py`` as a child.  ``chip`` None: the environment the
    harness was given, as the one-DataNode layout has always run it."""

    what = "reduction worker"

    def __init__(self, backend: str, fault: str = "", chip: int | None = None):
        argv = [os.path.join(HERE, "worker_entry.py"), "--backend", backend]
        if fault:
            argv += ["--fault", fault]
        env = None
        if chip is not None:
            env = dict(os.environ, **chip_env(chip))
        super().__init__(argv, env)
        hello = self.hello("listening")
        self.addr = tuple(hello["listening"])
        self.backend = hello["backend"]
        self.device = hello["device"]


# ------------------------------------------------------------------ layout

def layout(config: dict) -> int:
    """The number of DataNodes the configuration's cluster asks for, or
    SystemExit for a layout this harness does not run."""
    cl = config["cluster"]
    shape = (cl["namenodes"], cl["datanodes"], cl["workers"])
    if shape == (1, 1, 1):
        return 1
    n = cl["datanodes"]
    if (cl["namenodes"] == 1 and n > 1 and cl["workers"] == n
            and cl["replication"] == n and cl["chips"] >= n):
        return n
    raise SystemExit(
        f"this harness runs one NameNode with one DataNode and one worker, "
        f"or N DataNodes at replication N with a worker each on a chip "
        f"each; not namenodes {cl['namenodes']}, datanodes {n}, workers "
        f"{cl['workers']}, replication {cl['replication']}, chips "
        f"{cl['chips']}: a wider layout needs its own bring-up")


def start_workers(backend: str, fault: str, n: int) -> list:
    """One worker, as ever, or N at once, worker i on chip i (a native
    worker takes no chip)."""
    if n == 1:
        return [Worker(backend, fault)]
    chips = [i if backend == "tpu" else None for i in range(n)]
    with ThreadPoolExecutor(n) as pool:
        futs = [pool.submit(Worker, backend, fault, c) for c in chips]
    out, errors = [], []
    for f in futs:
        try:
            out.append(f.result())
        except Exception as e:  # noqa: BLE001 — stop the others, then raise
            errors.append(e)
    if errors:
        for w in out:
            w.stop()
        raise errors[0]
    return out


def tpu_chips_on_host() -> int:
    """TPU chips this machine lets its processes open, counted without JAX:
    the device nodes of the TPU driver (``/dev/accel<n>``) or of VFIO
    (``/dev/vfio/<group>``, one chip a group on a v5e host).  Not the PCI
    bus: a machine given one chip of a four-chip host shows all four
    there."""
    return len(glob.glob("/dev/accel[0-9]*")
               + glob.glob("/dev/vfio/[0-9]*"))


def device_count(workers: list) -> int:
    """The device count a run reports.  One worker: as JAX reports it there.
    N workers on a chip each: SystemExit unless each sees one chip and no two
    the same; then the host's chips (each worker sees one of them)."""
    if len(workers) == 1:
        return workers[0].device["count"]
    seen = [w.device.get("count") for w in workers]
    chips = {json.dumps(w.device.get("chip"), sort_keys=True)
             for w in workers}
    if any(c != 1 for c in seen) or len(chips) < len(workers):
        raise SystemExit(f"each of {len(workers)} workers must see one chip "
                         f"of its own; they report counts {seen} and chips "
                         f"{sorted(chips)}")
    return tpu_chips_on_host() or len(workers)


# ------------------------------------------------------------------- nodes

def missing_digests(dn, digests: list, lengths: list) -> int:
    """Reference digests absent from the DataNode's index, or present with
    another length."""
    missing = 0
    for i in range(0, len(digests), 8192):
        part = digests[i:i + 8192]
        want = lengths[i:i + 8192]
        found = dn.index.lookup_chunks(part)
        for d, ln in zip(part, want):
            loc = found[d]
            if loc is None or loc.length != ln:
                missing += 1
    return missing


def mirror_readings(dn) -> dict:
    """Mirror legs that failed outright (``BlockReceiver._note_mirror_failure``
    counts each and names the peer to ``DataNode.note_mirror_failure``) and
    blocks held only as coded segments (partial replicas)."""
    from hdrf_tpu.utils import metrics

    c = metrics.registry("block_receiver").snapshot()["counters"]
    return {"failed_legs": c.get("mirror_failures", 0),
            "partial_replicas": dn.mirror.report()["partial_blocks"]}


def serve(dn, state: dict, req: dict) -> dict:
    """One command on a DataNode in this process: what
    ``datanode_entry.py`` answers over its pipe."""
    import checks
    import faults

    cmd = req["cmd"]
    if cmd == "snapshot":
        return {"snapshot": snapshot(dn)}
    if cmd == "drain_seals":
        dn.containers.drain_seals()
    elif cmd == "flush_open":
        dn.containers.flush_open()
    elif cmd == "stored":
        return {"physical_bytes": dn.containers.physical_bytes(),
                "index": dn.index.stats()}
    elif cmd == "check_index":
        return {"missing": missing_digests(
            dn, [bytes.fromhex(h) for h in req["digests"]], req["lengths"])}
    elif cmd == "decode_sealed":
        return {"sealed": checks.decode_sealed(dn),
                "orphans": sum(dn.index.orphan_bytes().values()),
                "mirror": mirror_readings(dn),
                "backends": parent_backends()}
    elif cmd == "warm_reduce":
        # a DataNode that led no pipeline in set-up has a worker that has
        # reduced nothing, and its first block would lower the device
        # programs inside the window (about 5 s, cache or not): one block of
        # the cell's length through its own worker, as a block is reduced
        if dn._worker.stats().get("blocks_reduced", 0):
            return {"warmed": False}
        import numpy as np

        data = np.random.default_rng(req["seed"]).integers(
            0, 256, req["nbytes"], dtype=np.uint8).tobytes()
        dn._worker.reduce(data, dn.reduction_ctx.config.cdc)
        return {"warmed": True}
    elif cmd == "plant":
        faults.plant_in_datanode(dn, req["fault"])
    elif cmd == "phases_start":
        state["sampler"] = PhaseSampler(req["t0"])
        state["sampler"].start()
    elif cmd == "phases":
        return {"phases": state.pop("sampler").profile(req["t1"])}
    else:
        raise ValueError(f"unknown command {cmd!r}")
    return {}


class LocalNode:
    """The one DataNode of the harness's own process."""

    def __init__(self, dn):
        self.dn = dn
        self.dn_id = dn.dn_id
        self.remote = False

    def ask(self, **req) -> dict:
        return serve(self.dn, {}, req)

    def missing(self, table: dict) -> int:
        return missing_digests(self.dn, list(table), list(table.values()))

    def stop(self) -> None:
        pass     # MiniCluster stops it


class RemoteNode(Child):
    """``datanode_entry.py``: a DataNode in a process of its own."""

    what = "DataNode process"

    def __init__(self, spec: dict):
        super().__init__([os.path.join(HERE, "datanode_entry.py"),
                          json.dumps(spec)])
        self.dn_id = self.hello("dn_id")["dn_id"]
        self.remote = True

    def missing(self, table: dict) -> int:
        return self.ask(cmd="check_index", digests=[d.hex() for d in table],
                        lengths=list(table.values()))["missing"]


def each(nodes: list, fn) -> list:
    """``fn(node)`` for every node: inline for one, side by side for more
    (a drain or a decode on one DataNode does not wait for another's)."""
    if len(nodes) <= 1:
        return [fn(n) for n in nodes]
    with ThreadPoolExecutor(len(nodes)) as pool:
        return list(pool.map(fn, nodes))


def start_cluster(config: dict, workers: list):
    """(MiniCluster, nodes).  One DataNode: today's MiniCluster with it
    inside.  N: the MiniCluster holds the NameNode alone and DataNode i is
    a process of its own pointed at worker i."""
    from hdrf_tpu.testing.minicluster import MiniCluster

    cl = config["cluster"]
    n = layout(config)
    overrides = dict(cl.get("reduction", {}))
    if n == 1:
        overrides["worker_addr"] = list(workers[0].addr)
    mc = MiniCluster(n_datanodes=1 if n == 1 else 0,
                     replication=cl["replication"],
                     block_size=cl["block_size"],
                     container_size=cl["container_size"],
                     heartbeat_s=cl["heartbeat_interval_s"],
                     dead_node_s=cl["dead_node_interval_s"],
                     tpu_worker=False, backend="native",
                     reduction_overrides=overrides).start()
    if n == 1:
        return mc, [LocalNode(mc.datanodes[0])]
    nodes = []
    try:
        specs = [{"dn_id": f"dn-{i}",
                  "data_dir": os.path.join(mc.base_dir, f"dn{i}"),
                  "nn_addrs": [list(a) for a in mc.nn_addrs()],
                  "heartbeat_s": cl["heartbeat_interval_s"],
                  "container_size": cl["container_size"],
                  "reduction": dict(overrides,
                                    worker_addr=list(workers[i].addr))}
                 for i in range(n)]
        with ThreadPoolExecutor(n) as pool:
            futs = [pool.submit(RemoteNode, s) for s in specs]
        for f in futs:
            try:
                nodes.append(f.result())
            except Exception as e:  # noqa: BLE001 — raised once all are in
                nodes.append(e)
        bad = [x for x in nodes if isinstance(x, Exception)]
        if bad:
            raise bad[0]
        mc.wait_for_datanodes(n, timeout=60.0)
    except BaseException:
        for node in nodes:
            if not isinstance(node, Exception):
                node.stop()
        mc.stop()
        raise
    return mc, nodes


# ------------------------------------------------------------- phase clock

class PhaseSampler(threading.Thread):
    """Collects this process's phase-clock spans through the window.

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


def merge_phases(dns: list, harness: dict) -> dict:
    """One ``sources["phases"]`` from the partitions of N DataNode processes
    over the same window: exclusive seconds (phases, classes) and the other
    window-level numbers averaged over the DataNodes, so a share reads as one
    DataNode's; inclusive rows summed (``wall_max_s`` the largest), so a
    per-unit reading divides by every DataNode's units, and a share of the
    window by ``datanodes`` windows (``readers/span_stat.py``).  A name only the
    harness's process records — the NameNode's ``nn_rpc`` — comes from its
    own partition."""
    n = len(dns)
    out = {"datanodes": n}
    out.update({k: sum(p[k] for p in dns) / n
           for k in ("wall_s", "hidden_wait_s", "hideable_wait_s",
                     "overlap_efficiency", "attributed_frac")})
    for key in ("classes", "phases"):
        names = {k for p in dns for k in p[key]}
        out[key] = {k: sum(p[key].get(k, 0.0) for p in dns) / n
                    for k in sorted(names)}
    table: dict[str, dict] = {}
    for p in dns:
        for name, row in p.get("inclusive", {}).items():
            acc = table.setdefault(name, {"count": 0, "wall_s": 0.0,
                                          "wall_max_s": 0.0})
            acc["count"] += row["count"]
            acc["wall_s"] += row["wall_s"]
            acc["wall_max_s"] = max(acc["wall_max_s"], row["wall_max_s"])
            if "cpu_s" in row:
                acc["cpu_s"] = acc.get("cpu_s", 0.0) + row["cpu_s"]
    for name, secs in harness["phases"].items():
        if name not in out["phases"]:
            out["phases"][name] = secs
    for name, row in harness.get("inclusive", {}).items():
        table.setdefault(name, row)
    out["inclusive"] = table
    return out


# ---------------------------------------------------------------- counters

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
