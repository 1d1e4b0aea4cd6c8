#!/usr/bin/env python3
"""The benchmark's reduction worker: the one process that owns the chip.

It is the program's ``ReductionWorker`` unchanged, started as
``hdrf_tpu.server.reduction_worker.main`` starts it (compile cache placed by
``device_env.enable_compile_cache()``, backend ``tpu`` or exit 3), plus a
control channel the program does not have yet: JSON lines on stdin, one JSON
reply per line on stdout.  The harness, which never initialises JAX, uses it
to read the device's memory peak, to start and stop ``jax.profiler`` around a
few steady seconds of the window, and to have the trace reduced here (only
this process can hold JAX) by ``perfbench/trace_reduce.py``.  Its hello names
the chip it holds (``device["chip"]``).

Commands: ``{"cmd": "memory"}``, ``{"cmd": "trace_start", "dir": ...}``,
``{"cmd": "trace_stop"}``, ``{"cmd": "trace_reduce"}`` (after the window:
replies with the reduced summary), ``{"cmd": "quit"}``.  ``--fault`` plants a
fault for the tests under ``perfbench/tests`` and for the controls
(``perfbench/README.md``); the benchmark's own runs never pass it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                       # trace_reduce, faults
sys.path.insert(0, os.path.dirname(HERE))      # the program


def _reply(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="perfbench-worker")
    p.add_argument("--backend", default="tpu")
    p.add_argument("--fault", default="")
    args = p.parse_args(argv)

    from hdrf_tpu.server.reduction_worker import ReductionWorker
    from hdrf_tpu.utils import device_env

    if args.backend != "native":
        device_env.enable_compile_cache()
    if args.backend == "tpu":
        dev = device_env.device_info()
        if dev["platform"] != "tpu":
            print(f"perfbench worker: --backend tpu but JAX reports platform "
                  f"{dev['platform']!r} ({dev['kind']}); refusing to start",
                  file=sys.stderr)
            return 3
    w = ReductionWorker("127.0.0.1", 0, backend=args.backend)
    if args.fault:
        import faults

        faults.plant_in_worker(w, args.fault)
    w.start()
    _reply({"listening": list(w.addr), "backend": w.backend,
            "device": dict(w.device, chip=_chip(w.backend)),
            "pid": os.getpid()})

    trace_dir = None
    for line in sys.stdin:
        try:
            req = json.loads(line)
        except ValueError:
            continue
        cmd = req.get("cmd")
        try:
            if cmd == "quit":
                _reply({"ok": True})
                break
            if cmd == "memory":
                _reply({"ok": True, "memory": _memory(w.backend)})
            elif cmd == "trace_start":
                import jax

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                trace_dir = req["dir"]
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                _reply({"ok": True, "t_start": time.time()})
            elif cmd == "trace_stop":
                import jax

                t_stop = time.time()
                jax.profiler.stop_trace()
                _reply({"ok": True, "t_stop": t_stop})
            elif cmd == "trace_reduce":
                import trace_reduce

                _reply({"ok": True, "trace": trace_reduce.reduce_dir(
                    trace_dir, req.get("window_s"))})
            else:
                _reply({"ok": False, "error": f"unknown command {cmd!r}"})
        except Exception as e:  # noqa: BLE001 — the harness decides
            import traceback

            traceback.print_exc()
            _reply({"ok": False, "error": f"{type(e).__name__}: {e}"})
    # A worker that holds a chip takes more than 5 s to die of SIGTERM (PR
    # 22); nothing here needs an orderly interpreter shutdown.
    sys.stdout.flush()
    os._exit(0)


def _chip(backend: str) -> dict | None:
    """The chip this worker holds, as JAX and libtpu's environment name it:
    the harness refuses two workers on one chip."""
    if backend != "tpu":
        return None
    import jax

    d = jax.local_devices()[0]
    return {"id": d.id, "coords": list(getattr(d, "coords", None) or []),
            "visible": os.environ.get("TPU_VISIBLE_CHIPS")}


def _memory(backend: str) -> dict:
    if backend != "tpu":
        return {}
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"peak_bytes": peak}


if __name__ == "__main__":
    raise SystemExit(main())
