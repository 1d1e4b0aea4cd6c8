"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the few numbers the
per-layer readers take: device-busy seconds, seconds per device program, the
heaviest device operations and the longest idle gaps with what the host's
Python thread was doing in them.

What a v5e trace looks like (``perfbench/tests/data/one_block.xplane.pb``):
one plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one per HLO operation or kernel inside it) and ``Async XLA Ops``; and
``/host:CPU`` with one line per thread, ``python`` among them
(``PjitFunction(<fn>)``, ``np.asarray(jax.Array)``, ...).  All on one clock,
nanoseconds from the start of the session.

Busy is the union of the ``XLA Ops`` intervals (falling back to ``XLA
Modules`` where a backend writes no op line), averaged over the device
planes.  Only the process that holds JAX calls :func:`reduce_dir`; the
harness gets the summary as JSON.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

_FINGERPRINT = re.compile(r"\(\d+\)$")
TOP = 10


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _top(totals: dict[str, float]) -> list[list]:
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name[:120], secs] for name, secs in rows]


def program_name(event_name: str) -> str:
    """``jit__prep_impl(9433032683515456367)`` -> ``jit__prep_impl``."""
    return _FINGERPRINT.sub("", event_name)


def reduce_planes(planes, window_s: float | None = None) -> dict:
    """``planes``: objects with ``.name`` and ``.lines``; a line has ``.name``
    and ``.events``; an event ``.name``, ``.start_ns``, ``.duration_ns``.
    ``window_s``: the length of the traced window on the host's clock; the
    trace's clock starts with it, so the idle time before the first and
    after the last device operation counts among the gaps."""
    devices = []
    host_python: list[tuple[float, float, str]] = []
    for plane in planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                if ln.name == "python":
                    host_python = [(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in ln.events]
    busy_s = []
    programs: dict[str, list] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    gap_list: list[tuple[float, float]] = []
    n_events = 0
    for lines in devices:
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        spans = [] if op_line is None else [
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in op_line.events]
        n_events += len(spans)
        merged = _union([(s, e) for s, e, _ in spans])
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        mod_line = lines.get("XLA Modules")
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       program_name(e.name))
                      for e in ([] if mod_line is None else mod_line.events))
        for m0, m1, prog in mods:
            tally = programs.setdefault(prog, [0.0, 0])
            tally[0] += (m1 - m0) / 1e9
            tally[1] += 1
        starts = [m[0] for m in mods]
        for s, e, name in spans:
            # an operation is named within its program: "fusion.1" alone
            # says nothing
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            key = prog + "/" + name.split(" = ", 1)[0].lstrip("%")
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
        if not gap_list and merged:  # gaps of the first chip stand for all
            edges = [(0.0, 0.0), *merged]
            if window_s is not None:
                edges.append((max(window_s * 1e9, merged[-1][1]),) * 2)
            gap_list = [(a[1], b[0]) for a, b in zip(edges, edges[1:])
                        if b[0] > a[1]]
    for g0, g1 in sorted(gap_list, key=lambda g: g[0] - g[1])[:4 * TOP]:
        what, best = "host:no_python_event", 0.0
        for s, e, name in host_python:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                what, best = "host:" + name, ov
        gaps[what] = gaps.get(what, 0.0) + (g1 - g0) / 1e9
    return {
        "chips": len(devices),
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "device_events": n_events,
        "programs": {k: {"seconds": v[0], "count": v[1]}
                     for k, v in programs.items()},
        "device_ops": _top(ops),
        "idle_gaps": _top(gaps),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gap_list),
                             default=0.0) / 1e9,
    }


def reduce_file(path: str, window_s: float | None = None) -> dict:
    from jax.profiler import ProfileData

    out = reduce_planes(ProfileData.from_file(path).planes, window_s)
    out["trace_bytes"] = os.path.getsize(path)
    return out


def reduce_dir(trace_dir: str, window_s: float | None = None) -> dict:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(found[-1], window_s)
