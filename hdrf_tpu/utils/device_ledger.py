"""Device dispatch ledger: per-dispatch accounting for the TPU hot path.

Equivalent of the reference's per-stage GPU accounting (the utilization
counters DataDeduplicator.java:264-307 keeps around its chunk-scan calls and
the JNI timing in utilities.java:98-137) re-designed for the async XLA
dispatch model: on the earlier shared dev box ``block_until_ready`` acked at
ENQUEUE (PERF_NOTES.md), so completion is only taken as observed at the
readback that forces the result.  The ledger therefore records two moments the hot path
already has — dispatch (enqueue) and readback (the ``np.asarray`` /
``copy_to_host_async`` drain the caller performs anyway) — and never adds a
sync of its own.

Three kinds of records land in the ``device_ledger`` metrics registry and a
bounded event ring:

- ``dispatch(op, ...) -> token``: an enqueued device computation (counters
  ``dispatch_total``/``h2d_bytes_total``; first sighting of an ``(op, key)``
  shape key also counts ``compiles_total`` — the jit-cache-miss approximation).
- ``readback(token, ...)``: the forced completion of a prior dispatch
  (``readback_total``/``d2h_bytes_total``; histogram ``wait_us`` measures
  enqueue->forced-completion wall time, and the per-op family
  ``wait_us|op=<op>`` splits it by dispatch label on /prom; waits beyond
  the stall budget bump
  ``stall_total`` — the ~100 ms/dispatch norm vs the ~35 s VM stalls).
- ``transfer(kind, op, nbytes)``: a bare H2D/D2H copy with no compute
  (``h2d_bytes_total``/``d2h_bytes_total`` and a per-kind event).

Events carry the active trace context (utils/tracing.py) so span trees and
device work join into one timeline (the /traces chrome export); all event
fields are msgpack/JSON-safe scalars so they cross RPC unmodified.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any

from . import metrics, profiler, tracing

_M = metrics.registry("device_ledger")

# A readback wait past this is a stall (an awaited dispatch measured ~1 ms on
# the v5e host, PR 22; a first-shape compile also lands here).
STALL_BUDGET_S = float(os.environ.get("HDRF_DISPATCH_BUDGET_S", "5.0"))

_RING_MAX = 4096
_ring: deque[dict[str, Any]] = deque(maxlen=_RING_MAX)
_lock = threading.Lock()
_seen_keys: set[tuple] = set()
_next_id = [0]
_PROC = f"{os.path.basename(sys.argv[0] or 'py')}:{os.getpid()}"


class _Pending:
    """Timing token returned by dispatch(); closed by readback()."""

    __slots__ = ("op", "t0_wall", "t0", "batch", "h2d", "counted")

    def __init__(self, op: str, batch: int, h2d: int,
                 counted: bool = True) -> None:
        self.op = op
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()
        self.batch = batch
        self.h2d = h2d
        # Only counted tokens moved the outstanding-dispatches counter track
        # up at dispatch(); pending() tokens must not move it down.
        self.counted = counted


def _event(op: str, kind: str, *, t0: float, dur_us: float, batch: int,
           nbytes: int) -> int:
    ctx = tracing.current_context()
    ev = {
        "proc": _PROC, "op": op, "kind": kind, "t0": t0,
        "dur_us": round(dur_us, 1), "batch": batch, "bytes": nbytes,
        "trace_id": None if ctx is None else f"{ctx[0]:016x}",
        "span_id": None if ctx is None else f"{ctx[1]:016x}",
    }
    with _lock:
        _next_id[0] += 1
        ev["id"] = _next_id[0]
        _ring.append(ev)
    return ev["id"]


def dispatch(op: str, *, batch: int = 1, h2d_bytes: int = 0,
             key: tuple | None = None) -> _Pending:
    """Record an enqueued device computation; returns the timing token the
    matching ``readback`` closes.  ``key`` is a hashable shape signature —
    its first sighting counts as a compile event (jit cache miss)."""
    _M.incr("dispatch_total")
    _M.incr("dispatch_batch_total", batch)
    if h2d_bytes:
        _M.incr("h2d_bytes_total", h2d_bytes)
    if key is not None:
        k = (op, key)
        with _lock:
            fresh = k not in _seen_keys
            if fresh:
                _seen_keys.add(k)
        if fresh:
            _M.incr("compiles_total")
            _event(op, "compile", t0=time.time(), dur_us=0.0, batch=batch,
                   nbytes=0)
    # Enqueue marker: ring position (id) establishes dispatch ORDER, letting
    # tests pin pipeline structure — e.g. that the fused CDC path enqueues
    # its SHA dispatches BEFORE the cut-table readback completes (one fewer
    # awaited boundary than the XLA prep -> host-select -> SHA shape).
    _event(op, "enqueue", t0=time.time(), dur_us=0.0, batch=batch,
           nbytes=h2d_bytes)
    profiler.note_device_dispatch()
    return _Pending(op, batch, h2d_bytes)


def pending(op: str, *, batch: int = 1) -> _Pending:
    """Timing token WITHOUT counting a dispatch — for aggregate readbacks
    whose constituent dispatches were already recorded individually."""
    return _Pending(op, batch, 0, counted=False)


def readback(tok: _Pending | None, *, d2h_bytes: int = 0) -> None:
    """Record the forced completion of ``tok``'s dispatch.  Call AFTER the
    caller's own forcing readback (np.asarray / block_until_ready on a
    host-bound value) — the ledger never forces device work itself."""
    if tok is None:
        return
    dur = time.perf_counter() - tok.t0
    _M.incr("readback_total")
    if d2h_bytes:
        _M.incr("d2h_bytes_total", d2h_bytes)
    _M.observe("wait_us", dur * 1e6)
    _M.observe(f"wait_us|op={tok.op}", dur * 1e6)
    if dur > STALL_BUDGET_S:
        _M.incr("stall_total")
        _event(tok.op, "stall", t0=tok.t0_wall, dur_us=dur * 1e6,
               batch=tok.batch, nbytes=d2h_bytes)
    ev_id = _event(tok.op, "dispatch", t0=tok.t0_wall, dur_us=dur * 1e6,
                   batch=tok.batch, nbytes=tok.h2d + d2h_bytes)
    profiler.note_device_wait(tok.op, tok.t0_wall, tok.t0_wall + dur,
                              event_id=ev_id, counted=tok.counted)


def transfer(kind: str, op: str, nbytes: int) -> None:
    """Record a bare transfer (kind ``h2d`` or ``d2h``) with no compute."""
    _M.incr(f"{kind}_bytes_total", nbytes)
    _M.incr(f"{kind}_transfer_total")
    _event(op, kind, t0=time.time(), dur_us=0.0, batch=1, nbytes=nbytes)


def events_snapshot(limit: int = _RING_MAX) -> list[dict[str, Any]]:
    """Newest-last copy of the event ring (msgpack/JSON-safe dicts)."""
    with _lock:
        evs = list(_ring)
    return evs[-limit:]


def stamp() -> dict[str, int]:
    """Cheap counter stamp for delta accounting across a bench round."""
    snap = _M.snapshot()["counters"]
    return {k: snap.get(k, 0) for k in
            ("dispatch_total", "readback_total", "compiles_total",
             "stall_total", "h2d_bytes_total", "d2h_bytes_total")}


def delta(before: dict[str, int]) -> dict[str, int]:
    """Counter movement since ``before`` (a ``stamp()`` result)."""
    now = stamp()
    return {k: now[k] - before.get(k, 0) for k in now}
