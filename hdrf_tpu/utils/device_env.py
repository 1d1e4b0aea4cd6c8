"""What a device-owning entry point sets up before it touches JAX, and what
it can report about the device afterwards.

Exactly one process owns a chip (the reduction worker in the served
deployment; ``python -m hdrf_tpu.benchmarks`` / ``bench.py`` when they run
the device path themselves).  That process calls :func:`enable_compile_cache`
first — a cold worker otherwise recompiles every program (tens of seconds
for the match scan) on every start — and may answer :func:`device_info`,
:func:`compile_seconds` and :func:`probe_box` for a parent that must stay
off JAX (``chip_smoke.py`` prints them).

The reference loads its native codecs inside the DataNode at daemon start
(DataNode.java:438 startDataNode; the JNI timing of utilities.java:98-137);
here the device lives in a process of its own, so its start-up facts are
this module's to report.
"""

from __future__ import annotations

import os
import statistics
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_compile_s: dict[str, float] = {}
_compile_lock = threading.Lock()
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it itself, so
    nothing is set in code.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored): the path is part of the cache
    key, so it is never derived from a pid, a time or a temp dir.  Also
    starts the per-program compile-seconds tally."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return path


def cache_dir() -> str | None:
    """The directory JAX caches compiles in, as its config has it (None for
    a process that never imported JAX, e.g. a native worker)."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.config.jax_compilation_cache_dir


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE:
        with _compile_lock:
            name = str(kw.get("fun_name", "?"))
            _compile_s[name] = _compile_s.get(name, 0.0) + duration


def compile_seconds() -> dict[str, float]:
    """Seconds spent in backend compiles (or persistent-cache loads) per
    jitted program name since :func:`enable_compile_cache`."""
    with _compile_lock:
        return {k: round(v, 3) for k, v in _compile_s.items()}


def device_info() -> dict:
    """Platform, kind and count as JAX reports them (initialises the
    backend: only the device-owning process calls this)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def probe_box(mb: int = 64, reps: int = 5) -> dict:
    """The facts of the machine the constants in this repo were never
    fitted to: host cores, awaited-dispatch round trip, H2D and D2H rates.
    Every transfer is closed by a dependent readback, so an enqueue-time
    acknowledgement cannot pass for completion.  Notes, not metrics."""
    import jax
    import numpy as np

    med = statistics.median
    bump = jax.jit(lambda a: a + 1)
    last = jax.jit(lambda a: a[-1])
    flip = jax.jit(lambda a: a ^ 1)
    s = jax.device_put(np.int32(0))
    int(bump(s))
    rtts = []
    for _ in range(4 * reps):
        t0 = time.perf_counter()
        int(bump(s))
        rtts.append(time.perf_counter() - t0)
    rtt = med(rtts)
    x = np.random.default_rng(0).integers(0, 256, mb << 20, dtype=np.uint8)
    d = jax.device_put(x)
    int(last(d))
    flip(d).block_until_ready()
    h2d, d2h = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        d = jax.device_put(x)
        int(last(d))
        h2d.append(time.perf_counter() - t0)
        y = flip(d)
        y.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(y)
        d2h.append(time.perf_counter() - t0)
    return {"nproc": os.cpu_count(),
            "dispatch_rtt_ms": rtt * 1e3,
            "h2d_mb_s": mb / max(med(h2d) - rtt, 1e-9),
            "d2h_mb_s": mb / med(d2h),
            "probe_mb": mb, "probe_reps": reps}
