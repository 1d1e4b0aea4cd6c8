"""Load generator: client processes, one ``HdrfClient`` each, closed loop.

Each client is a process of its own (``multiprocessing`` spawn context; it
never imports JAX), so the load does not share the interpreter the NameNode
and the DataNode run in.  The harness talks to it over a pipe:

    ready  <- after ``driver.prepare`` (data made from the seed)
    connect(nn_addr) / setup / run(t_release, seconds) / check / quit

``run`` sleeps until ``t_release`` (the barrier: one host clock), then drives
``driver.run`` until ``t_release + seconds``: no operation starts after that,
operations in flight are finished and counted.  Every operation comes back as
a record ``{kind, path, bytes, t0, t1, ok, err}`` on the host's wall clock.
"""

from __future__ import annotations

import multiprocessing
import os
import resource
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


class Ctx:
    """What a driver sees of its client."""

    def __init__(self, spec: dict):
        self.seed = spec["seed"]
        self.idx = spec["idx"]
        self.clients = spec["clients"]
        self.params = spec["params"]
        self.config = spec["config"]
        self.generator = None
        self.state: dict = {}

    def op(self, kind: str, path: str, nbytes: int, fn):
        """Run one operation, timed on the wall clock; an exception is a
        failed operation, not a dead client."""
        rec = {"kind": kind, "path": path, "bytes": nbytes, "ok": True,
               "err": None, "t0": time.time()}
        try:
            rec["out"] = fn()
        except Exception as e:  # noqa: BLE001 — counted, reported, judged
            rec["ok"], rec["err"] = False, f"{type(e).__name__}: {e}"[:300]
            rec["out"] = None
        rec["t1"] = time.time()
        return rec


def _cpu_s() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def _strip(ops: list) -> list:
    return [{k: v for k, v in op.items() if k != "out"} for op in ops]


def client_main(conn, spec: dict) -> None:
    sys.path.insert(0, HERE)
    sys.path.insert(0, spec["repo"])
    try:
        import manifest

        driver = manifest.plugin("drivers", spec["driver"])
        ctx = Ctx(spec)
        ctx.generator = manifest.plugin("generators", spec["generator"])
        if spec.get("fault"):
            import faults

            faults.plant_in_client(spec["fault"])
        t0 = time.time()
        driver.prepare(ctx)
        conn.send(("ready", {"prepare_s": time.time() - t0}))
        client = None
        while True:
            cmd, arg = conn.recv()
            if cmd == "quit":
                break
            if cmd == "connect":
                from hdrf_tpu.client.filesystem import HdrfClient

                client = HdrfClient(tuple(arg), name=f"perfbench-{ctx.idx}")
                conn.send(("done", None))
            elif cmd == "setup":
                conn.send(("done", _strip(driver.setup(ctx, client))))
            elif cmd == "run":
                t_release, seconds = arg
                time.sleep(max(t_release - time.time(), 0.0))
                cpu0 = _cpu_s()
                ops = driver.run(ctx, client, t_release + seconds)
                from hdrf_tpu.utils import metrics

                conn.send(("done", {
                    "ops": _strip(ops), "cpu_s": _cpu_s() - cpu0,
                    "late_s": (ops[0]["t0"] - t_release) if ops else 0.0,
                    "counters":
                        metrics.registry("client").snapshot()["counters"]}))
            elif cmd == "check":
                conn.send(("done", driver.check(ctx, client)))
        if client is not None:
            client.close()
    except Exception:  # noqa: BLE001 — the harness must hear of it
        conn.send(("error", traceback.format_exc()[-4000:]))
    finally:
        conn.close()


class Clients:
    """The harness's side: start, talk to and stop the client processes."""

    def __init__(self, specs: list[dict]):
        mp = multiprocessing.get_context("spawn")
        self.procs, self.conns = [], []
        for spec in specs:
            ours, theirs = mp.Pipe()
            p = mp.Process(target=client_main, args=(theirs, spec),
                           name=f"perfbench-client-{spec['idx']}")
            p.start()
            theirs.close()
            self.procs.append(p)
            self.conns.append(ours)

    def _recv(self, conn, timeout: float):
        if not conn.poll(timeout):
            raise TimeoutError("a load-generator process did not answer "
                               f"within {timeout:.0f} s")
        kind, payload = conn.recv()
        if kind == "error":
            raise RuntimeError("load-generator process failed:\n" + payload)
        return payload

    def wait_ready(self, timeout: float = 600.0) -> list:
        return [self._recv(c, timeout) for c in self.conns]

    def call(self, cmd: str, arg=None, timeout: float = 900.0) -> list:
        """Send ``cmd`` to every client, then gather every answer."""
        for c in self.conns:
            c.send((cmd, arg))
        return [self._recv(c, timeout) for c in self.conns]

    def stop(self) -> None:
        for c in self.conns:
            try:
                c.send(("quit", None))
            except (OSError, ValueError):
                pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join()
        for c in self.conns:
            c.close()
