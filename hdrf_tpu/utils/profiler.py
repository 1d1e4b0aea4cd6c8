"""Write-path critical-path profiler: phase-attributed block timelines.

The reference measures its write path with coarse per-op rate counters
(DataNodeMetrics.java:553-560 ``addWriteBlockOp``/``addPacketAckRoundTripTimeNanos``)
— enough to say *that* a write was slow, never *where* the time went.  This
module is the missing decomposition.  The DataNode's host has cores to
spare (13 beside the benchmark's chip) and ONE interpreter: every handler
thread, the seal thread and the periodic ticks take turns at it, so host
work is the scarce class and a wait is good exactly when host work runs
under it (PERF_NOTES.md:round 4 drew that for a one-vCPU host; it holds
for one interpreter lock on any number of cores):

- Every block write opens a :class:`BlockTimeline` (ambient via contextvar,
  the bf1-buffer lifetime of BlockReceiver.java:877-897) into which named
  phase spans land — ``recv``, ``dedup_lookup``, ``wal_commit``,
  ``device_wait``, ``container_io``, ``mirror_stream``, ``ack`` — each a
  plain ``(phase, t0, t1, thread)`` tuple (one list append; no locks on the
  hot path, no syncs).  The device ledger (utils/device_ledger.py) feeds
  ``device_wait`` spans and event-id links at its existing readback hook, so
  host phases and device work join into one timeline.
- :func:`profile_spans` is the overlap accountant.  Its **exclusive
  partition** gives every instant of a wall-clock window to one of four
  classes — ``host_busy`` > ``device_busy`` > ``transport_wait`` > ``idle``
  (priority order; host work always owns the interpreter, so wait time
  under it is *hidden*, the desirable state) — and within the winning class
  to one phase (``PHASE_ORDER``), and computes ``overlap_efficiency`` =
  hidden wait / hideable wait: the numbers the gap-attribution table
  (tools/gap_report.py) and the benchmark's ``*_pct`` metrics read.  A share
  of the window says who OWNED it, not what anyone paid: with four streams
  ``container_io`` owns every instant any thread is inside it, and a
  background phase owns only what no foreground phase claims.
- Beside the partition, the **inclusive table** (PR 35): by span name the
  count of spans, their own lengths summed over whatever threads
  (thread-wall: what the blocks, containers or ticks of a window themselves
  paid), the longest, and — for the spans that carry it — their thread's
  CPU, which tells what a unit cost from what it waited (for the
  interpreter, a lock, a socket).  ``cpu_phase(name)`` takes
  ``time.thread_time()`` at both ends; it is for spans that are few (a
  block, a container, a read, a tick), never for a packet run's.
- A fifth class, ``COVER``, is in the inclusive table alone: covering spans
  of a unit of work — ``seal_queue`` / ``seal`` / ``seal_index`` a
  container, ``seal_drain`` a caller of ``drain_seals()``, ``dn_block`` /
  ``dn_read`` a finished timeline, ``mirror_push`` / ``mirror_ingest`` a
  mirror leg's two ends — which the sweep skips, so they take no instant
  from ``idle`` or from the phases beneath them.
- Counter tracks (in-flight blocks, outstanding dispatches, WAL queue depth)
  sample on every change into a bounded ring, rendered as Chrome ``C``
  events by tracing.chrome_trace for the /traces?format=chrome export.

Finished timelines observe per-phase latency histograms
(``phase_us|phase=<name>`` — utils/prom.py renders the ``|k=v`` key suffix
as extra labels) and overlap gauges into the ``write_profiler`` registry, so
every surface the observability spine already reaches (/prom, /metrics,
status_http.py, the gateway) serves them with no extra wiring.

The READ path (server/block_sender.py serve_read, the short-circuit server
and the EC degraded read) opens the same machinery via
:func:`read_timeline`: phases ``index_lookup``/``cache_probe``/
``container_decode`` (host), ``ec_gather``/``net_send`` (transport) and the
ledger-fed ``device_wait`` partition one serve's wall clock identically,
observing ``phase_us|op=read,phase=<name>`` histograms plus read-side
overlap gauges into the ``read_profiler`` registry — the serving-path twin
the reference never decomposes (DataNodeMetrics.java:553-560 counts read
ops, never where a read's time went).

One clock for both processes of the served write path (PR 25): the same
:func:`phase` call is the span in the DataNode, the stage span in the
reduction worker, the cumulative per-name self seconds the worker exports
as ``<stage>_s`` (:func:`cumulative`), and — in a process that has already
imported JAX — a ``jax.profiler.TraceAnnotation("hdrf.<name>")`` on the
device trace's timeline.  A phase too fine to record span by span
(one CRC32C per 64 KiB packet) is timed with :func:`lap` instead: its
seconds gather on the thread and land as one span a 4 MiB stride.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Iterable, Iterator

from . import metrics, tracing

_M = metrics.registry("write_profiler")
_R = metrics.registry("read_profiler")

# Overlap classes, in wall-clock partition PRIORITY order (PERF_NOTES round
# 4: the one interpreter is the scarce resource — an interval where host
# work runs counts host_busy even when device/transport waits are in flight;
# those waits are then HIDDEN, which is the state the pipeline wants).
HOST, DEVICE, TRANSPORT = "host", "device", "transport"
# A covering span of a unit of work (a container's whole seal, a block's
# whole stay on its thread): counted in the inclusive table, skipped by the
# sweep, so it takes no instant from ``idle`` or from the phases beneath it.
COVER = "cover"
CLASSES = ("host_busy", "device_busy", "transport_wait", "idle")

PHASE_CLASS = {
    "recv": TRANSPORT, "mirror_stream": TRANSPORT, "ack": TRANSPORT,
    "dedup_lookup": HOST, "wal_commit": HOST, "container_io": HOST,
    "reduce_compute": HOST, "checksum": HOST,
    "device_wait": DEVICE,
    # Read-path phases (server/block_sender.py serve_read/read_logical):
    # index/cache/decode burn the single vCPU; stripe gathers and the
    # packet run to the client are network waits the host could hide.
    "index_lookup": HOST, "cache_probe": HOST, "container_decode": HOST,
    "ec_gather": TRANSPORT, "net_send": TRANSPORT,
    # The steps of a read's service between lookup and send, each under
    # its own name (one span a container or a read, never one a chunk):
    # container_load a sealed container's file read, or an open
    # container's wanted bytes taken from its lane under the lane's lock
    # (storage/container_store.py), a whole-block scheme's stored bytes;
    # container_decode the codec alone; chunk_copy the wanted chunks out of
    # the decoded container and into the reply's bytes; read_admit a
    # handler waiting for a read slot or the read plane's permit (HOST for
    # lock_wait's reason below: it sits inside the covering read_serve
    # span, and as a transport wait every queued second would read as
    # read_serve's); read_serve the covering span of serve_read's service,
    # which owns only what none of them names.
    "container_load": HOST, "chunk_copy": HOST, "read_admit": HOST,
    "read_serve": HOST,
    # A reader parked on the read coalescer's shared decode future
    # (server/read_plane.py): a hideable wait — the real decode burns the
    # vCPU under the LEAD reader's mirrored container_decode span, which
    # wins the interval's class, so this only attributes the queue/window
    # slack that nothing else covers.
    "decode_wait": TRANSPORT,
    # Control-plane RPC service-time phases (proto/rpc.py _serve_one):
    # frame/reply socket IO are transport waits; everything between them
    # is NN host work.  lock_wait is deliberately HOST, not transport —
    # the whole dispatch sits inside the covering ``handler`` span (HOST),
    # and the exclusive sweep resolves same-class overlaps by PHASE_ORDER,
    # so classifying it transport would hide every queued-on-the-namesystem
    # second under ``handler`` and the contention table would read clean.
    "frame_read": TRANSPORT, "reply": TRANSPORT,
    "dispatch_queue": HOST, "lock_wait": HOST, "locked": HOST,
    "serialize": HOST, "handler": HOST,
    # What ``recv`` hid and what nothing covered (PR 25).  packet_verify is
    # the CRC32C of arriving packets (a run's parse, verify and one copy in
    # iter_packet_runs; read_packet_crc's); worker_send the DN forwarding a
    # stride frame to the worker; seal_* the background container seal (its two
    # hop legs are waits, the file work is this interpreter's); nn_rpc one
    # NameNode request, frame read to reply sent; heartbeat_stats /
    # block_scan the two periodic DataNode ticks.
    "packet_verify": HOST, "worker_send": TRANSPORT,
    "seal_send": TRANSPORT, "seal_wait": TRANSPORT, "seal_write": HOST,
    "nn_rpc": HOST, "heartbeat_stats": HOST, "block_scan": HOST,
    # The reduction worker's stage clock (server/reduction_worker.py):
    # leaf spans of one op, exported as ``<stage>_s``.  ``block`` is the
    # covering span of a whole reduce op: its self seconds are what no
    # stage explains (the reply among them), the closure reading.
    "ingest_wait": TRANSPORT, "stage_h2d": HOST, "prep_wait": DEVICE,
    "select": HOST, "sha_wait": DEVICE, "scan_wait": DEVICE, "emit": HOST,
    "block": TRANSPORT,
    # A compress op's upload leg in the worker: a sealed container's stride
    # frames read into one buffer and each verified (one span an op; the
    # reduce op's strides stay ``ingest_wait`` + ``packet_verify``).  HOST:
    # the CRC32C and the copy out of the loopback socket are this
    # process's seconds, and the sender's sum of the next frame runs
    # beside them.
    "seal_ingest": HOST,
    # Units of work on the DataNode's served path, inclusive table only
    # (PR 35).  A container: ``seal_queue`` from the rollover's ``put`` to
    # the seal thread's ``get``; ``seal`` everything the seal thread (or,
    # inline, the appending thread) does for it, its thread's CPU with it;
    # ``seal_index`` the ``on_seal`` hook inside that (the index's lock).
    # The container's own timeline needs no ring of its own: it is the seal
    # thread's spans between its ``seal`` span's ends, and its
    # ``seal_queue`` ends where that begins.  ``seal_drain`` is a caller
    # inside ``drain_seals()``: in a benchmark window, the tail after the
    # last ack.  ``dn_block`` / ``dn_read`` are one block's / one read's
    # timeline, first instant to last, with the receiving / serving
    # thread's CPU over it.
    "seal_queue": COVER, "seal": COVER, "seal_index": COVER,
    "seal_drain": COVER, "dn_block": COVER, "dn_read": COVER,
    # The DN -> DN leg of reduced block mirroring (server/block_receiver.py),
    # never one span a chunk.  Push side: ``mirror_read`` the needed chunks'
    # index lookup and their read out of this DataNode's own store (the
    # store's read phases nest inside it), ``mirror_stream`` the op frame,
    # the lengths frame and the delta's stride frames written,
    # ``mirror_wait`` the need frame, the hop-status frame and the final
    # ack (the chain below waited on).  Relay side: ``mirror_recv`` one span
    # a frame read of the delta stream (lengths, stride frames, trailer).
    # Covering, with thread CPU: ``mirror_push`` a push, ``mirror_ingest`` a
    # relayed block (a middle relay's own push inside it).
    "mirror_read": HOST, "mirror_wait": TRANSPORT, "mirror_recv": TRANSPORT,
    "mirror_push": COVER, "mirror_ingest": COVER,
}
_COVERING = frozenset(n for n, c in PHASE_CLASS.items() if c == COVER)

# Deterministic attribution order when several phases of the winning class
# overlap inside one elementary interval (rare: host phases are serial on
# this host) — first match wins.  Nested read phases (index_lookup inside a
# read_serve window) resolve to the innermost by listing it first.
PHASE_ORDER = ("device_wait", "prep_wait", "sha_wait", "scan_wait",
               "wal_commit", "container_io", "dedup_lookup",
               "reduce_compute", "packet_verify", "checksum", "seal_write",
               "stage_h2d", "select", "emit", "seal_ingest",
               "index_lookup", "cache_probe", "read_admit",
               # a push's read of the chunks it ships: ahead of the store's
               # read phases that nest inside it
               "mirror_read",
               "container_load", "container_decode", "chunk_copy",
               "read_serve",
               # RPC phases: lock_wait/locked win attribution inside the
               # covering ``handler`` window; handler last among them so it
               # only owns the time no finer phase explains.
               "lock_wait", "locked", "dispatch_queue", "serialize",
               "handler",
               # periodic ticks and the NameNode's whole request: they own
               # only host seconds no write-path phase claims
               "heartbeat_stats", "block_scan", "nn_rpc",
               "worker_send", "recv", "mirror_recv", "mirror_stream",
               "mirror_wait", "ack",
               "seal_send", "seal_wait",
               "ec_gather", "decode_wait", "net_send", "frame_read", "reply",
               # the worker's covering span last: it claims only the
               # seconds no stage does
               "ingest_wait", "block")


_ORDER_RANK = {name: i for i, name in enumerate(PHASE_ORDER)}


def _phase_rank(name: str) -> tuple[int, str]:
    """Attribution rank: place in PHASE_ORDER, phases outside it after all
    of those, by name."""
    return (_ORDER_RANK.get(name, len(_ORDER_RANK)), name)


def phase_class(name: str) -> str:
    """Overlap class of a phase; unknown names default to host work."""
    return PHASE_CLASS.get(name, HOST)


# Wall clock: phase spans must share a time base with tracing.Span.t0 and
# the device ledger's event t0 so one chrome export aligns them all.  Bound
# to the builtin itself (one call per read, not two); always looked up as
# a module global so tests can substitute a settable clock.
_now = time.time
# The calling thread's CPU seconds, for the few spans that carry them
# (``cpu_phase(name)``, a timeline's covering span); a module global
# for the same reason.
_thread_cpu = time.thread_time


_PROC = f"{os.path.basename(sys.argv[0] or 'py')}:{os.getpid()}"

_RING_MAX = 1024          # finished timelines
_SPAN_RING_MAX = 65536    # raw spans (window_profile's source)
_COUNTER_RING_MAX = 8192  # counter-track samples

_lock = threading.Lock()
_timelines: deque["BlockTimeline"] = deque(maxlen=_RING_MAX)
_read_timelines: deque["BlockTimeline"] = deque(maxlen=_RING_MAX)
_span_ring: deque[tuple] = deque(maxlen=_SPAN_RING_MAX)
_counter_ring: deque[dict[str, Any]] = deque(maxlen=_COUNTER_RING_MAX)
_counters: dict[str, float] = {}
_counter_id = [0]

# Laps gathered into one span (:func:`lap`): 64 packets of 64 KiB are one
# 4 MiB stride, the grain of the worker's other stages.
_LAP_EVERY = 64


class _ThreadState:
    """One thread's open-phase stack, cumulative self seconds by phase name
    and gathered laps.  Only its own thread writes it, so the hot path
    takes no lock."""

    __slots__ = ("tid", "stack", "cum", "laps")

    def __init__(self, tid: int) -> None:
        self.tid = tid
        self.stack: list["phase"] = []
        self.cum: dict[str, float] = {}
        self.laps: dict[str, list] = {}   # name -> [seconds, count]


_tls = threading.local()
_threads: dict[int, _ThreadState] = {}   # tid -> state (watchdog probe,
_cum_dead: dict[str, float] = {}         # cumulative()); dead threads fold
_FOLD_AT = 64


def _fold(cum: dict[str, float]) -> None:
    for name, secs in cum.items():
        _cum_dead[name] = _cum_dead.get(name, 0.0) + secs


def _thread_state() -> _ThreadState:
    """This thread's state, registered on first use.  Registration is the
    one place that takes the lock; it also folds the totals of threads that
    have ended (one connection = one thread in both daemons) so the
    registry stays small."""
    tid = threading.get_ident()
    ts = _tls.state = _ThreadState(tid)
    with _lock:
        if len(_threads) >= _FOLD_AT:
            alive = {t.ident for t in threading.enumerate()}
            for dead in [k for k in _threads if k not in alive]:
                _fold(_threads.pop(dead).cum)
        if tid in _threads:              # a reused thread id: keep its sums
            _fold(_threads[tid].cum)
        _threads[tid] = ts
    return ts

_current: contextvars.ContextVar["BlockTimeline | None"] = \
    contextvars.ContextVar("hdrf_block_timeline", default=None)


# ------------------------------------------------------------ block timeline


class BlockTimeline:
    """Phase spans + device-ledger links for one block write."""

    __slots__ = ("block_id", "nbytes", "trace_id", "t0", "t1", "spans",
                 "ledger_ids")

    def __init__(self, block_id: int, nbytes: int = 0,
                 t0: float | None = None) -> None:
        self.block_id = block_id
        self.nbytes = nbytes
        ctx = tracing.current_context()
        self.trace_id = None if ctx is None else f"{ctx[0]:016x}"
        self.t0 = _now() if t0 is None else t0
        self.t1: float | None = None
        self.spans: list[tuple] = []    # (phase, t0, t1, thread[, cpu_s])
        self.ledger_ids: list[int] = []       # device-ledger event ids

    def add_span(self, phase: str, t0: float, t1: float,
                 thread: int = 0) -> None:
        self.spans.append((phase, t0, t1, thread))

    def finish(self, t1: float | None = None) -> None:
        if self.t1 is None:
            self.t1 = _now() if t1 is None else t1

    def profile(self, inclusive: bool = True) -> dict[str, Any]:
        end = self.t1 if self.t1 is not None else _now()
        return profile_spans(self.spans, self.t0, end, nbytes=self.nbytes,
                             inclusive=inclusive)

    def snapshot(self) -> dict[str, Any]:
        """JSON-safe dump (the gap_report/--input interchange shape)."""
        return {"block_id": self.block_id, "nbytes": self.nbytes,
                "trace_id": self.trace_id, "t0": self.t0, "t1": self.t1,
                "spans": [[sp[0], sp[1], sp[2]] for sp in self.spans],
                "ledger_ids": list(self.ledger_ids),
                "profile": self.profile()}


# --------------------------------------------------------- overlap accountant


def profile_spans(spans: Iterable, t0: float, t1: float,
                  nbytes: int = 0, inclusive: bool = True) -> dict[str, Any]:
    """Partition [t0, t1] into the four exclusive overlap classes and
    per-phase exclusive seconds via a boundary sweep, and (``inclusive``)
    sum every span's own length by name beside it.

    ``spans`` yields ``(phase, s0, s1[, thread[, cpu_s]])``.
    The class partition sums exactly to the wall clock (``idle`` is the
    remainder by construction).  ``overlap_efficiency`` = wait time hidden
    under host work / total device+transport wait time (1.0 when there was
    nothing to hide); ``attributed_frac`` = share of wall covered by at
    least one named phase (the >= 95% gap_report acceptance bar).

    The partition says what share of the WINDOW a phase owned (one phase an
    instant, whatever the number of threads inside it); the ``inclusive``
    table says what the spans of a name THEMSELVES paid: ``count``,
    ``wall_s`` (their lengths summed, clamped to the window, from whatever
    threads — four threads a second inside a phase are four seconds),
    ``wall_max_s`` and, where the spans carried their thread's CPU,
    ``cpu_s`` (a span cut by the window keeps the same share of its CPU as
    of its wall).  Spans of class ``COVER`` are in that table alone.
    """
    wall = max(t1 - t0, 0.0)
    classes = dict.fromkeys(CLASSES, 0.0)
    phases: dict[str, float] = {}
    hidden = hideable = 0.0
    events: list[tuple[float, int, str]] = []
    table: dict[str, list] = {}     # name -> [count, wall, wall_max, cpu]
    for sp in spans:
        name, s0, s1 = sp[0], max(sp[1], t0), min(sp[2], t1)
        # (a span too short for the clock's last digit, inside the window,
        # still counts in the table: a drain that found the queue empty)
        if s1 > s0 or (s1 == s0 and sp[1] == sp[2]):
            if inclusive:
                dur = s1 - s0
                row = table.get(name)
                if row is None:
                    row = table[name] = [0, 0.0, 0.0, None]
                row[0] += 1
                row[1] += dur
                if dur > row[2]:
                    row[2] = dur
                if len(sp) > 4:
                    row[3] = (row[3] or 0.0) + _cut(sp, t0, t1)[4]
            if name in _COVERING or s1 == s0:
                continue
            events.append((s0, 1, name))
            events.append((s1, -1, name))
    events.sort(key=lambda e: e[0])

    # phases open right now, by class (only those with a positive count):
    # the winner of an interval is the open phase of the winning class that
    # comes first in PHASE_ORDER (then by name) — found among the few open
    # phases, not by walking the whole order, since this sweep runs at every
    # block's end in the interpreter that receives
    active: dict[str, dict[str, int]] = {HOST: {}, DEVICE: {}, TRANSPORT: {}}
    prev = t0
    i, n = 0, len(events)
    while i < n:
        t = events[i][0]
        if t > prev:
            dt = t - prev
            if active[HOST]:
                win, wc = "host_busy", HOST
            elif active[DEVICE]:
                win, wc = "device_busy", DEVICE
            elif active[TRANSPORT]:
                win, wc = "transport_wait", TRANSPORT
            else:
                win, wc = "idle", None
            classes[win] += dt
            if active[DEVICE] or active[TRANSPORT]:
                hideable += dt
                if win == "host_busy":
                    hidden += dt
            if wc is not None:
                open_now = active[wc]
                attr = (next(iter(open_now)) if len(open_now) == 1 else
                        min(open_now, key=_phase_rank))
                phases[attr] = phases.get(attr, 0.0) + dt
            prev = t
        while i < n and events[i][0] == t:
            _, kind, name = events[i]
            open_cls = active[phase_class(name)]
            count = open_cls.get(name, 0) + kind
            if count > 0:
                open_cls[name] = count
            else:
                open_cls.pop(name, None)
            i += 1
    used = (classes["host_busy"] + classes["device_busy"]
            + classes["transport_wait"])
    classes["idle"] = wall - used  # exact partition by construction
    out = {
        "wall_s": wall,
        "classes": classes,
        "phases": phases,
        "hidden_wait_s": hidden,
        "hideable_wait_s": hideable,
        "overlap_efficiency": hidden / hideable if hideable > 0 else 1.0,
        "attributed_frac": used / wall if wall > 0 else 1.0,
    }
    if inclusive:
        out["inclusive"] = {
            name: ({"count": n, "wall_s": w, "wall_max_s": m} if c is None
                   else {"count": n, "wall_s": w, "wall_max_s": m,
                         "cpu_s": c})
            for name, (n, w, m, c) in table.items()}
    if nbytes:
        out["bytes"] = nbytes
        out["mb_per_s"] = nbytes / wall / (1 << 20) if wall > 0 else 0.0
    return out


# --------------------------------------------------------------- ambient API


@contextlib.contextmanager
def block_timeline(block_id: int, nbytes: int = 0) -> Iterator[BlockTimeline]:
    """Open the ambient timeline for one block write; on exit the finished
    timeline lands in the ring and its per-phase histograms + overlap gauges
    are observed into the ``write_profiler`` registry."""
    c0 = _thread_cpu()     # both reads outside [t0, t1]: no idle added
    tl = BlockTimeline(block_id, nbytes)
    tok = _current.set(tl)
    counter_add("inflight_blocks", 1)
    try:
        yield tl
    finally:
        _current.reset(tok)
        counter_add("inflight_blocks", -1)
        tl.finish()
        cpu = _thread_cpu() - c0
        _cover("dn_block", tl, cpu)
        with _lock:
            _timelines.append(tl)
        _observe_finished(tl)


@contextlib.contextmanager
def read_timeline(block_id: int, nbytes: int = 0,
                  cover: str | None = "dn_read") -> Iterator[BlockTimeline]:
    """Open the ambient timeline for one block READ (serve_read /
    short-circuit serve / EC degraded read).  Same BlockTimeline machinery
    and exclusive-class partition as the write side — reconstruct code
    below it records ``index_lookup``/``container_decode``/``ec_gather``
    phases via the ordinary :func:`phase` ambient channel, and the device
    ledger's readback hook still lands ``device_wait`` spans — but finished
    timelines ring separately and observe into the ``read_profiler``
    registry as ``phase_us|op=read,phase=<name>`` histograms, so the read
    families sit next to the write families on /prom.  ``cover`` names the
    covering span the finished timeline leaves in the ring; ``None`` for a
    timeline that is no read's service (the short-circuit fd grant, which
    comes before every local client's read and would halve the mean)."""
    c0 = _thread_cpu()     # both reads outside [t0, t1]: no idle added
    tl = BlockTimeline(block_id, nbytes)
    tok = _current.set(tl)
    counter_add("inflight_reads", 1)
    try:
        yield tl
    finally:
        _current.reset(tok)
        counter_add("inflight_reads", -1)
        tl.finish()
        cpu = _thread_cpu() - c0
        if cover is not None:
            _cover(cover, tl, cpu)
        with _lock:
            _read_timelines.append(tl)
        _observe_finished_read(tl)


def _cover(name: str, tl: BlockTimeline, cpu: float) -> None:
    """A finished timeline's covering span, as long as the timeline itself
    and with the CPU its thread spent under it: into the ring alone (the
    timeline has ``t0`` / ``t1`` already, and the sweep that follows on
    this thread is not the block's)."""
    _span_ring.append((name, tl.t0, tl.t1, threading.get_ident(), cpu))


def _observe_finished_read(tl: BlockTimeline) -> None:
    prof = tl.profile(inclusive=False)
    for name, s in prof["phases"].items():
        _R.observe(f"phase_us|op=read,phase={name}", s * 1e6)
    _R.observe("read_wall_us", prof["wall_s"] * 1e6)
    _R.gauge("overlap_efficiency", prof["overlap_efficiency"])
    _R.gauge("attributed_frac", prof["attributed_frac"])
    _R.incr("reads_profiled")


def read_timelines_snapshot(limit: int = _RING_MAX) -> list[dict[str, Any]]:
    """Newest-last finished READ timelines as JSON-safe dicts — the
    read-path acceptance smoke's and slo_report's in-process source."""
    with _lock:
        tls = list(_read_timelines)
    return [t.snapshot() for t in tls[-limit:]]


def current_timeline() -> BlockTimeline | None:
    return _current.get()


@contextlib.contextmanager
def bind_timeline(tl: BlockTimeline | None) -> Iterator[BlockTimeline | None]:
    """Adopt an EXISTING timeline as this thread's ambient one.

    Contextvars do not propagate into worker threads, so a helper thread
    doing one block's work (the read coalescer's batched decode —
    server/read_plane.py) would otherwise record its spans ring-only and
    the per-block overlap accountant would never see the work it hid.
    Binding does NOT finish the timeline or touch the inflight counter —
    ownership stays with the opening :func:`block_timeline` frame."""
    tok = _current.set(tl)
    try:
        yield tl
    finally:
        _current.reset(tok)


def _observe_finished(tl: BlockTimeline) -> None:
    prof = tl.profile(inclusive=False)
    for name, s in prof["phases"].items():
        _M.observe(f"phase_us|phase={name}", s * 1e6)
    _M.observe("block_wall_us", prof["wall_s"] * 1e6)
    _M.gauge("overlap_efficiency", prof["overlap_efficiency"])
    _M.gauge("attributed_frac", prof["attributed_frac"])
    _M.incr("blocks_profiled")


def _record(span: tuple) -> None:
    """One finished span: onto the ambient timeline and into the ring.
    ``list.append`` and ``deque.append`` are atomic in CPython — no lock."""
    tl = _current.get()
    if tl is not None:
        tl.spans.append(span)
    _span_ring.append(span)


def record_span(name: str, t0: float, t1: float) -> None:
    """A span timed by its caller on :func:`_now` (the NameNode's request,
    which starts before its handler thread knows there is one)."""
    _record((name, t0, t1, threading.get_ident()))


_trace_me = None    # jax.profiler.TraceAnnotation, once JAX is imported


def _bind_trace_me():
    """The device trace's annotation class if this process has ALREADY
    imported JAX (never imports it: the DataNode beside a worker must stay
    off JAX, and then pays one dict probe per span)."""
    global _trace_me
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    if cls is not None:
        _trace_me = cls
    return cls


class phase:
    """Record a named phase span (ambient timeline + global ring + this
    thread's cumulative self seconds).  Cost is two clock reads, a list
    append and a deque append — safe on the per-packet path.  While a
    ``jax.profiler`` session is live the span is also a
    ``TraceAnnotation("hdrf.<name>")`` on the trace's clock."""

    __slots__ = ("name", "t0", "child", "_ts", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "phase":
        try:
            ts = _tls.state
        except AttributeError:
            ts = _thread_state()
        self._ts = ts
        ts.stack.append(self)
        self.child = 0.0
        self._ann = None
        cls = _trace_me
        if cls is None and "jax" in sys.modules:
            cls = _bind_trace_me()
        if cls is not None and cls.is_enabled():
            self._ann = cls("hdrf." + self.name)
            self._ann.__enter__()
        self.t0 = _now()
        return self

    def _close(self) -> list:
        """Off the thread's stack and off the trace; nothing recorded."""
        stack = self._ts.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:       # closed out of order (abandoned generator)
            stack.remove(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return stack

    def __exit__(self, *exc) -> None:
        t1 = _now()
        stack = self._close()
        ts = self._ts
        name, t0 = self.name, self.t0
        dur = t1 - t0
        if stack:
            stack[-1].child += dur
        cum = ts.cum
        cum[name] = cum.get(name, 0.0) + dur - self.child
        span = (name, t0, t1, ts.tid)
        tl = _current.get()       # _record, inlined on the per-packet path
        if tl is not None:
            tl.spans.append(span)
        _span_ring.append(span)


class cpu_phase(phase):
    """A :class:`phase` that also reads ``time.thread_time()`` at both
    ends, inside the wall reads, and carries the difference as a fifth
    field of its span: what the span cost its thread against what it
    waited (for the interpreter, a lock, a socket).  Two more clock reads
    of 6.5 us each on the benchmark's host: for a span a block, a
    container, a read or a tick, never one a packet run, a stride or a
    chunk.  A class of its own so that a plain span costs what it did."""

    __slots__ = ("_c0",)

    def __enter__(self) -> "cpu_phase":
        phase.__enter__(self)
        self._c0 = _thread_cpu()
        return self

    def __exit__(self, *exc) -> None:
        cpu = _thread_cpu() - self._c0
        t1 = _now()
        stack = self._close()
        ts = self._ts
        dur = t1 - self.t0
        if stack:
            stack[-1].child += dur
        ts.cum[self.name] = ts.cum.get(self.name, 0.0) + dur - self.child
        _record((self.name, self.t0, t1, ts.tid, cpu))


def lap(name: str, t0: float) -> None:
    """One lap, [``t0`` (a :func:`mark`), now], of a phase too fine to record
    span by span: a CRC32C per 64 KiB packet, 2 048 a block, on the
    thread that receives.  The lap's seconds gather on the thread;
    every ``_LAP_EVERY`` laps of a name (one 4 MiB stride) everything the
    thread has gathered lands as spans (:func:`flush_laps`)."""
    t1 = _now()
    try:
        ts = _tls.state
    except AttributeError:
        ts = _thread_state()
    acc = ts.laps.get(name)
    if acc is None:
        acc = ts.laps[name] = [0.0, 0]
    acc[0] += t1 - t0
    acc[1] += 1
    if acc[1] >= _LAP_EVERY:
        flush_laps(t1)


def flush_laps(end: float | None = None) -> None:
    """Land this thread's gathered laps: ONE span per name, as long as the
    name's laps together, laid end to end backwards from ``end``.  The laps
    were disjoint intervals of one thread since its last flush, so the
    spans fit in that stretch and overlap neither each other nor an
    earlier flush: the partition books each name the seconds it measured,
    placed within a stride of where they were spent.  They do lie over the
    thread's own per-packet spans (``recv``, ``ack``), which lose those
    seconds, while the moments the laps really ran stay uncovered: what
    ``recv``/``ack`` lose reads as unattributed, their sum is kept."""
    ts = getattr(_tls, "state", None)
    if ts is None:
        return
    if end is None:
        end = _now()
    total = 0.0
    for name, acc in ts.laps.items():
        secs = acc[0]
        if acc[1]:
            acc[0], acc[1] = 0.0, 0
            ts.cum[name] = ts.cum.get(name, 0.0) + secs
            _record((name, end - total - secs, end - total, ts.tid))
            total += secs
    if total and ts.stack:
        ts.stack[-1].child += total


def timed_iter(name: str, it: Iterable) -> Iterator:
    """Wrap an iterator so each ``next()`` wait becomes one phase span —
    the per-packet ``recv`` attribution of the client-stream wait."""
    src = iter(it)
    while True:
        p = phase(name)
        p.__enter__()
        try:
            item = next(src)
        except StopIteration:
            p._close()          # the exhausted next() leaves no span
            return
        except BaseException:
            p.__exit__()
            raise
        p.__exit__()
        yield item


def thread_phase(thread_id: int | None = None) -> str | None:
    """Innermost phase currently open on a thread — the watchdog's
    cross-thread stall attribution probe."""
    if thread_id is None:
        thread_id = threading.get_ident()
    ts = _threads.get(thread_id)
    if ts is None:
        return None
    try:
        return ts.stack[-1].name
    except IndexError:
        return None


def cumulative() -> dict[str, float]:
    """Self seconds by phase name since process start, over every thread
    (a span's self seconds are its wall less its nested spans', so leaf
    stages under a covering span sum to the covering span's wall): the
    reduction worker's ``<stage>_s`` counters."""
    with _lock:
        out = dict(_cum_dead)
        states = list(_threads.values())
    for ts in states:
        for name, s in list(ts.cum.items()):
            out[name] = out.get(name, 0.0) + s
    return out


def thread_cumulative() -> dict[str, float]:
    """This thread's share of :func:`cumulative` — two of these bracket one
    op on its handler thread."""
    try:
        return dict(_tls.state.cum)
    except AttributeError:
        return {}


# ----------------------------------------------------------- device linkage


def note_device_dispatch() -> None:
    """Device-ledger hook: a dispatch was enqueued (counter track only)."""
    counter_add("outstanding_dispatches", 1)


def note_device_wait(op: str, t0: float, t1: float,
                     event_id: int | None = None,
                     counted: bool = True) -> None:
    """Device-ledger hook at readback: the [enqueue, forced-completion]
    window becomes a ``device_wait`` span, linked to the ledger event id on
    the ambient timeline."""
    if counted:
        counter_add("outstanding_dispatches", -1)
    tl = _current.get()
    if tl is not None and event_id is not None:
        tl.ledger_ids.append(event_id)
    _record(("device_wait", t0, t1, threading.get_ident()))


# ------------------------------------------------------------ counter tracks


def counter_add(name: str, delta: float) -> float:
    with _lock:
        v = _counters.get(name, 0.0) + delta
        _counters[name] = v
        _sample_locked(name, v)
    _M.gauge(name, v)
    return v


def counter_set(name: str, value: float) -> None:
    with _lock:
        _counters[name] = value
        _sample_locked(name, value)
    _M.gauge(name, value)


def _sample_locked(name: str, value: float) -> None:
    _counter_id[0] += 1
    _counter_ring.append({"t": _now(), "name": name, "value": value,
                          "proc": _PROC, "id": _counter_id[0]})


def counters_snapshot(limit: int = _COUNTER_RING_MAX) -> list[dict[str, Any]]:
    """Newest-last counter-track samples (chrome ``C`` event source)."""
    with _lock:
        out = list(_counter_ring)
    return out[-limit:]


# ----------------------------------------------------- run-level windowing


def mark() -> float:
    """Wall-clock stamp for window_profile (bench round boundaries)."""
    return _now()


def window_spans(t0: float, t1: float) -> list[tuple]:
    """Spans from ANY thread overlapping [t0, t1], clamped to it — the
    cross-thread view run-level accounting needs (the bench's commit worker
    records on its own thread; a contextvar would never see it)."""
    return [(sp[0], max(sp[1], t0), min(sp[2], t1), sp[3]) if len(sp) == 4
            else _cut(sp, t0, t1)
            for sp in _span_ring.copy() if sp[2] > t0 and sp[1] < t1]


def _cut(sp: tuple, t0: float, t1: float) -> tuple:
    """A span that carries its thread's CPU, clamped to [t0, t1]: cut by
    an edge it keeps the same share of its CPU as of its wall."""
    name, s0, s1, tid, cpu = sp
    a, b = max(s0, t0), min(s1, t1)
    if (a, b) != (s0, s1):
        cpu *= (b - a) / (s1 - s0)
    return (name, a, b, tid, cpu)


def window_profile(t0: float, t1: float, nbytes: int = 0) -> dict[str, Any]:
    """Overlap profile of everything recorded in [t0, t1] across threads —
    the bench's ``phase_profile`` JSON stamp."""
    return profile_spans(window_spans(t0, t1), t0, t1, nbytes=nbytes)


# ------------------------------------------------------------- introspection


def timelines_snapshot(limit: int = _RING_MAX) -> list[dict[str, Any]]:
    """Newest-last finished timelines as JSON-safe dicts (profiles
    included) — gap_report's in-process source."""
    with _lock:
        tls = list(_timelines)
    return [t.snapshot() for t in tls[-limit:]]


def reset() -> None:
    """Drop rings + counters (tests / gap_report smoke isolation); the
    write_profiler registry's cumulative metrics are left alone."""
    with _lock:
        _timelines.clear()
        _read_timelines.clear()
        _span_ring.clear()
        _counter_ring.clear()
        _counters.clear()
