"""Co-located TPU reduction worker: a separate process owning the device.

The north-star deployment (BASELINE.json; SURVEY.md §2.4 "bulk transport"):
*"BlockReceiver streams 128 MB block packets over gRPC to a co-located TPU
worker; bytes land in HBM."*  This daemon is that worker — the TPU-side
equivalent of the reference's in-process JNI boundary (DataXceiver ->
libnayuki/codecs), lifted into its own process so the DataNode host stays
device-free:

- **Streaming ingest**: the DataNode forwards block packets as they are
  received, gathered into one frame per device upload stride (the stride
  wire of proto/datatransfer.py: the client's packets and the CRCs it
  sent, one ``sendmsg``); the worker reads each frame into one buffer,
  verifies it in one native call and stages it to HBM while later packets
  are still arriving, then assembles the resident block device-side —
  bytes land in HBM before the stream even finishes.
- **Compute**: CDC candidate scan + bucketed SHA-256 via
  ops.resident.ResidentReducer on the resident image; LZ4 match discovery
  via ops.lz4_tpu.  Only cuts/digests/compressed bytes return to the DN —
  O(chunks), not O(block).
- **Completion**: the DN's admission slot is held across the round trip
  and released when the response lands (the DDRunner completion-callback
  role, DDRunner.java:37-53, with real backpressure instead of ticket
  arithmetic).

Run standalone: ``python -m hdrf_tpu.server.reduction_worker --port 0``.
"""

from __future__ import annotations

import os
import socket
import socketserver
import threading
import time
from typing import Any

import numpy as np

from hdrf_tpu import native
from hdrf_tpu.config import CdcConfig
from hdrf_tpu.proto import datatransfer as dt
from hdrf_tpu.proto.rpc import recv_frame, send_frame
from hdrf_tpu.reduction import accounting
from hdrf_tpu.utils import metrics, profiler, retry, tracing

_M = metrics.registry("reduction_worker")
_TR = tracing.tracer("reduction_worker")

# Device upload stride for streaming ingest: big enough to amortize the
# per-transfer cost, small enough that HBM staging overlaps the tail of
# the network stream.  Also the frame of the reduce op's upload leg: the
# DataNode sends one when this many bytes are pending, the worker uploads
# each as it is.  A sealed container crosses the hop in frames of the same
# length (``dt.write_frames``): the sender sums frame k+1 while the worker
# reads and verifies frame k.
_STRIDE = dt.STRIDE

# The stage clock (utils/profiler.py, PR 25): a reduce op is a run of leaf
# ``profiler.phase`` spans no finer than one stride under one covering span
# (``block``), so their self seconds close on the op's wall clock.  ``stats``
# exports each as ``<stage>_s``; the three legacy sums are made of them, per
# op, on the handler thread.
_INGEST_STAGES = ("ingest_wait", "packet_verify", "stage_h2d")
_COMPRESS_STAGES = ("scan_wait", "emit")

# (cuts, digests) of a reduce op that streamed no byte
_NO_CHUNKS = (np.empty(0, np.int64), np.empty((0, 32), np.uint8))


def _stage_seconds(before: dict) -> dict:
    """Self seconds by stage this thread spent since ``before``
    (``profiler.thread_cumulative()`` at the op's start)."""
    return {k: v - before.get(k, 0.0)
            for k, v in profiler.thread_cumulative().items()}


class ReductionWorker:
    """The worker daemon.  Thread-per-connection like the DN xceiver; the
    device work itself is serialized by JAX's stream, so concurrent jobs
    interleave at dispatch granularity."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 backend: str = "auto"):
        from hdrf_tpu.ops import dispatch as ops_dispatch

        self.backend = ops_dispatch.resolve_backend(backend)
        # What the device path runs on, as JAX reports it — carried by
        # ``ping`` so a parent that must stay off JAX can report it.  The
        # native backend never initialises JAX.
        if self.backend == "tpu":
            from hdrf_tpu.utils import device_env

            self.device = device_env.device_info()
        else:
            self.device = {"platform": None, "kind": None, "count": 0}
        self._reducers: dict[tuple, Any] = {}
        self._lz4 = None
        self._stats_lock = threading.Lock()
        # ingest_s / reduce_s / compress_s: the streamed reduce's two legs
        # (packets in, verify, H2D strides; then scan, select, SHA and
        # readbacks) and compress jobs (match scan + emit) — sums of the
        # stage clock's seconds, kept because a metric and a test read them
        # hop_frames / hop_packets: stride frames that carried bytes and
        # the segments in them (64 a frame when a client sends 64 KiB
        # packets; 1 when a whole buffer came through ``reduce``)
        # seal_frames / seal_segments: the same of the compress ops' upload
        # leg (4 segments of 1 MiB a full frame)
        self._stats = {"blocks_reduced": 0, "bytes_reduced": 0,
                       "compress_jobs": 0, "ingest_s": 0.0,
                       "reduce_s": 0.0, "compress_s": 0.0,
                       "hop_frames": 0, "hop_packets": 0,
                       "seal_frames": 0, "seal_segments": 0}
        if self.backend == "tpu":
            # zeros between a block's true length and its rung of the
            # block-length ladder, summed over reduce ops; a native backend
            # pads nothing and has no such key
            self._stats["bytes_padded"] = 0
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self) -> None:
                sock = self.request
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                try:
                    while True:
                        req = recv_frame(sock)
                        outer._dispatch(sock, req)
                except (ConnectionError, OSError):
                    return

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, port), Handler)
        self._thread: threading.Thread | None = None
        from hdrf_tpu.utils.watchdog import StallWatchdog

        self.watchdog = StallWatchdog("reduction_worker", registry=_M)

    @property
    def addr(self) -> tuple[str, int]:
        return self._server.server_address

    def start(self) -> "ReductionWorker":
        metrics.registry("native").gauge("crc32c_hw", native.crc32c_hw())
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="reduction-worker", daemon=True)
        self._thread.start()
        self.watchdog.start()
        return self

    def stop(self) -> None:
        self.watchdog.stop()
        self._server.shutdown()
        self._server.server_close()

    # ------------------------------------------------------------- dispatch

    def _dispatch(self, sock: socket.socket, req: dict) -> None:
        op = req.get("op")
        # Resume the DN-side span carried in the request frame (the op-header
        # continueTraceSpan pattern, Receiver.java:94-98, extended across the
        # DN->worker process boundary) — only around compute ops so ping /
        # stats / trace polls never pollute the span sink.
        trace = req.get("_trace")
        try:
            if op in ("reduce", "compress", "compress_batch"):
                # Rebind the DN's remaining deadline budget (hop-by-hop,
                # same transport slot as _trace) so worker-side sub-calls
                # inherit what's left of the end-to-end budget.
                with retry.bind_remaining(req.get(retry.DEADLINE_KEY)), \
                        self.watchdog.track(f"worker.{op}"), \
                        _TR.span(f"worker.{op}",
                                 parent=tuple(trace) if trace else None) as sp:
                    sp.annotate("backend", self.backend)
                    if op == "reduce":
                        self._op_reduce(sock, req)
                    elif op == "compress":
                        self._op_compress(sock, req)
                    else:
                        self._op_compress_batch(sock, req)
            elif op == "ping":
                send_frame(sock, {"ok": True, "backend": self.backend,
                                  "device": self.device})
            elif op == "stats":
                send_frame(sock, self.stats())
            elif op == "device_report":
                send_frame(sock, self._device_report(bool(req.get("probe"))))
            elif op == "traces":
                from hdrf_tpu.utils import device_ledger

                send_frame(sock, {
                    "daemon": "reduction_worker",
                    "spans": tracing.all_span_snapshots(),
                    "ledger": device_ledger.events_snapshot(),
                    "counters": profiler.counters_snapshot()})
            else:
                send_frame(sock, {"error": "NoSuchOp", "message": str(op)})
        except (ConnectionError, OSError):
            raise
        except Exception as e:  # noqa: BLE001 — errors cross the wire
            _M.incr("op_errors")
            send_frame(sock, {"error": type(e).__name__, "message": str(e)})

    def stats(self) -> dict:
        """Counters, flat and numeric (a reader takes deltas of whatever
        keys it finds): ops and bytes; ``<stage>_s`` — cumulative self
        seconds of every phase this process recorded, a stage its backend
        does not run left out; the three sums of them; once ``_prep`` has
        run (a device backend's first block), ``prep_retries`` — reduce
        ops whose candidates overflowed its capacity and ran it again —,
        ``select_words`` — the non-zero candidate words cut selection
        walked, summed over blocks — and the gauge ``prep_cap_words``, the
        capacity rung in use (registry ``resident``, ops/resident.py); on a
        device backend
        ``bytes_padded`` (the block-length ladder's waste, above) and
        ``prep_shapes`` — the (padded length, capacity) pairs this worker's
        reducers have dispatched ``_prep`` at, retries too: each a program
        to compile or to fetch from the cache, so its growth over a window
        is the reduce ops that met a new one; ``crc32c_hw`` — 1 where this
        process's ``native.crc32c`` runs on the CPU's instruction, 0 where
        it is the table loop (the gauge of registry ``native``); the
        process's CPU seconds and a wall clock to set them against."""
        with self._stats_lock:
            out = dict(self._stats)
        for name, secs in profiler.cumulative().items():
            out[name + "_s"] = secs
        prep = metrics.registry("resident").snapshot()
        if "prep_cap_words" in prep["gauges"]:
            out["prep_retries"] = prep["counters"].get("prep_retries", 0)
            out["select_words"] = prep["counters"].get("select_words", 0)
            out["prep_cap_words"] = prep["gauges"]["prep_cap_words"]
        if self.backend == "tpu":
            out["prep_shapes"] = sum(len(r.prep_shapes)
                                     for r in list(self._reducers.values()))
        out["crc32c_hw"] = native.crc32c_hw()
        out["cpu_s"] = time.process_time()
        out["wall_s"] = time.perf_counter()
        return out

    def _device_report(self, probe: bool) -> dict:
        """Device-side facts for a parent that never touches JAX: dispatch
        counters, awaited dispatches per op with their enqueue-to-readback
        wait (``resident.prep_retry`` is the CDC candidate-overflow retry),
        the LZ4 stage's give-way counters,
        compile seconds per program and the cache directory; with
        ``probe`` also the box (device_env.probe_box — runs device work)
        and, on a chip, the Pallas SHA kernel's odd-lane-rows self-check."""
        from hdrf_tpu.utils import device_env, device_ledger

        hists = metrics.registry("device_ledger").snapshot()["histograms"]
        out = {"backend": self.backend, "device": self.device,
               "cache_dir": device_env.cache_dir(),
               "compile_s": device_env.compile_seconds(),
               "ledger": device_ledger.stamp(),
               "ops": {k.split("op=", 1)[1]:
                       {"n": h["count"], "mean_ms": h["mean"] / 1e3,
                        "max_ms": h["max"] / 1e3}
                       for k, h in hists.items() if "|op=" in k},
               "lz4": metrics.registry("lz4_tpu").snapshot()["counters"]}
        if probe and self.backend == "tpu":
            from hdrf_tpu.ops import sha256_pallas

            out["box"] = device_env.probe_box()
            if self.device["platform"] == "tpu":   # Mosaic runs nowhere else
                out["sha_odd_rows_ok"] = \
                    sha256_pallas.selfcheck_odd_lane_rows()
        return out

    def _reducer(self, cdc: CdcConfig):
        key = (cdc.mask_bits, cdc.min_chunk, cdc.max_chunk)
        r = self._reducers.get(key)
        if r is None:
            from hdrf_tpu.ops.resident import ResidentReducer

            r = self._reducers[key] = ResidentReducer(cdc)
        return r

    def _op_reduce(self, sock: socket.socket, req: dict) -> None:
        """Stride frames -> (cuts, digests).  Both backends read the same
        wire (``_strides``): a frame is one verified buffer.  TPU backend:
        each goes to HBM as it is, one device upload DURING the stream; the
        resident block is assembled device-side.  A frame that fails its
        check raises out of here, so the DataNode gets the error frame every
        worker failure gets and falls back in-process.  ``block`` is the
        op's one covering span: what it keeps as self seconds (the reply
        among them) is what no stage explains."""
        cdc = CdcConfig(mask_bits=req["mask_bits"],
                        min_chunk=req["min_chunk"],
                        max_chunk=req["max_chunk"])
        before = profiler.thread_cumulative()
        with profiler.phase("block"):
            if self.backend == "tpu":
                cuts, digs = self._reduce_streaming_tpu(sock, cdc)
            else:
                from hdrf_tpu.ops import dispatch as ops_dispatch

                bufs = list(self._strides(sock))
                if bufs:
                    with profiler.phase("ingest_wait"):   # the join it held
                        buf = (np.concatenate(bufs) if len(bufs) > 1
                               else bufs[0])
                    cuts, digs = ops_dispatch.chunk_and_fingerprint(
                        buf, cdc, self.backend)  # phase "reduce_compute"
                else:
                    cuts, digs = _NO_CHUNKS
            nbytes = int(cuts[-1]) if len(cuts) else 0
            # the sums go in with the count, before the reply: a ``stats``
            # call that sees the block sees its seconds
            took = _stage_seconds(before)
            ingest = sum(took.get(k, 0.0) for k in _INGEST_STAGES)
            with self._stats_lock:
                self._stats["blocks_reduced"] += 1
                self._stats["bytes_reduced"] += nbytes
                self._stats["ingest_s"] += ingest
                self._stats["reduce_s"] += sum(took.values()) - ingest
            send_frame(sock, {"cuts": np.asarray(cuts, np.int64).tobytes(),
                              "digests": np.ascontiguousarray(digs).tobytes()})
        _M.incr("blocks_reduced")
        accounting.record_worker_bytes("reduce", nbytes)

    def _strides(self, sock: socket.socket):
        """The reduce op's upload leg: yields each stride frame's bytes as
        one fresh ``uint8`` array, every segment checked against the CRC32C
        it was carried with (the client's own for a served write).  One
        ``ingest_wait`` and one ``packet_verify`` span a frame; a stream
        read to its end adds its frames and segments to ``stats``."""
        frames = segments = 0
        last = False
        while not last:
            with profiler.phase("ingest_wait"):
                buf, lens, crcs, last = dt.read_stride(sock)
            if buf.size:
                with profiler.phase("packet_verify"):
                    dt.verify_stride(buf, lens, crcs)
                frames += 1
                segments += len(lens)
                yield buf
        with self._stats_lock:
            self._stats["hop_frames"] += frames
            self._stats["hop_packets"] += segments

    def _reduce_streaming_tpu(self, sock: socket.socket, cdc: CdcConfig):
        """A block lands on the device at its rung of the block-length
        ladder (``ops.resident.block_rung``), so its programs are the
        rung's and not its length's.  Full strides go up as they arrive,
        as they are; what is left when the stream ends — the short last
        frame, or a whole block under one stride — is laid into ONE host
        buffer that reaches to the rung and goes up in one ``device_put``:
        no zeros made, nothing joined on the device for a block under a
        stride, and for a longer one a ``concatenate`` whose shapes the
        count of full strides and the rung decide (a few dozen in all up to
        128 MiB).  A frame that is not a full stride before the last one (a
        client with odd packets) is held with everything behind it."""
        import jax
        import jax.numpy as jnp

        from hdrf_tpu.ops.resident import block_rung

        parts: list = []        # resident device strides (uploads in flight)
        held: list = []         # host frames that wait for the rung
        up = total = 0
        for buf in self._strides(sock):
            total += buf.size
            if held or buf.size != _STRIDE:
                held.append(buf)
                continue
            with profiler.phase("stage_h2d"):
                parts.append(jax.device_put(buf))  # async H2D: lands in
                # HBM while the next frame streams in
            up += buf.size
        if not total:
            return _NO_CHUNKS
        with profiler.phase("stage_h2d"):
            size = block_rung(total)
            if size > up:
                tail = np.zeros(size - up, np.uint8)
                if held:
                    np.concatenate(held, out=tail[:total - up])
                parts.append(jax.device_put(tail))
            block = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        # prep_wait, select and sha_wait are recorded where they happen
        # (ops/resident.py)
        r = self._reducer(cdc)
        job = r.submit(block, n=total)
        with self._stats_lock:
            self._stats["bytes_padded"] += size - total
        r.start_sha(job)
        return r.finish(job)

    def _note_compress(self, jobs: int, before: dict) -> None:
        took = _stage_seconds(before)
        with self._stats_lock:
            self._stats["compress_jobs"] += jobs
            self._stats["compress_s"] += sum(took.get(k, 0.0)
                                             for k in _COMPRESS_STAGES)

    def _seal_payload(self, sock: socket.socket, size: int) -> np.ndarray:
        """The compress ops' upload leg (``dt.read_frames``): every stride
        frame landed in ONE buffer of the ``size`` the request stated — no
        parts, no join — and each frame's segments checked against their
        CRC32Cs in one native call, all under the stage ``seal_ingest``.  A
        frame that fails its check raises only once the rest of the stream
        is read, so the error frame leaves on a connection still in step."""
        with profiler.phase("seal_ingest"):
            buf, frames, segments = dt.read_frames(sock, size, read_on=True)
        with self._stats_lock:
            self._stats["seal_frames"] += frames
            self._stats["seal_segments"] += segments
        return buf

    def _op_compress(self, sock: socket.socket, req: dict) -> None:
        from hdrf_tpu.ops import dispatch as ops_dispatch

        data = self._seal_payload(sock, int(req["size"]))
        before = profiler.thread_cumulative()
        # the device match scan inside records ``scan_wait`` (absent when
        # the scan is bypassed); ``emit`` keeps the rest as self seconds
        with profiler.phase("emit"):
            out = ops_dispatch.block_compress(req.get("codec", "lz4"), data,
                                              self.backend)
        self._note_compress(1, before)
        dt.send_with_payloads(sock, {"sizes": [len(out)]}, [out])
        _M.incr("compress_jobs")
        accounting.record_worker_bytes("compress", len(data))

    def _op_compress_batch(self, sock: socket.socket, req: dict) -> None:
        """N payloads in one round trip (a DN sealing several container
        lanes at once): req["sizes"] splits the one buffer the frames land
        in into views.  On the TPU backend equal-size payloads compress as
        ONE device program with one grouped readback (block_compress_batch)
        — without this op each lane pays its own dispatch + readback round
        trip through the transport."""
        from hdrf_tpu.ops import dispatch as ops_dispatch

        sizes = [int(v) for v in req.get("sizes", [])]
        blob = self._seal_payload(sock, sum(sizes))
        ends = np.cumsum(sizes, dtype=np.int64).tolist()
        datas = [blob[e - n:e] for e, n in zip(ends, sizes)]
        before = profiler.thread_cumulative()
        with profiler.phase("emit"):
            outs = ops_dispatch.block_compress_batch(
                req.get("codec", "lz4"), datas, self.backend)
        self._note_compress(len(sizes), before)
        dt.send_with_payloads(sock, {"sizes": [len(o) for o in outs]}, outs)
        _M.incr("compress_jobs", len(sizes))
        accounting.record_worker_bytes("compress", blob.size)


# ------------------------------------------------------------------ client


class WorkerError(IOError):
    """Worker-side failure (connect/protocol/compute).  DISTINCT from the
    caller's own stream errors: a DN forwarding client packets must treat a
    dead worker as 'fall back to in-process compute' but a dead CLIENT as a
    failed write — conflating them would commit truncated blocks."""


class WorkerClient:
    """DN-side handle on the co-located worker.  One pooled connection per
    concurrent job (connections are cheap on loopback; the pool bound comes
    from the DN's admission slots holding across the round trip).

    Resilience contract (utils/retry.py): every data-path op runs under a
    payload-scaled deadline budget — ``deadline_s`` base plus
    ``deadline_s_per_mb`` accrued per streamed MiB, clamped by any ambient
    end-to-end deadline — so a HUNG worker costs at most the remaining
    budget, not the reference's fixed 600 s socket timeout.  When a
    ``breaker`` (retry.CircuitBreaker) is attached, data-path ops check it
    BEFORE connecting: a DEAD worker costs zero connect attempts while the
    breaker is open, and the half-open probe re-admits the edge when the
    worker returns.  Worker-side failures record breaker outcomes; errors
    from the caller's own packet iterator never touch the breaker (they
    are not evidence about the worker).  ping/stats/traces stay outside
    the breaker so observability polls never consume the half-open probe.
    """

    def __init__(self, addr, timeout: float = 600.0,
                 deadline_s: float | None = None,
                 deadline_s_per_mb: float = 0.0,
                 breaker: "retry.CircuitBreaker | None" = None):
        self._addr = (addr[0], int(addr[1]))
        self._timeout = timeout if deadline_s is None else deadline_s
        self._per_mb = float(deadline_s_per_mb)
        self._breaker = breaker
        self._pool: list[socket.socket] = []
        self._lock = threading.Lock()

    def set_addr(self, addr) -> None:
        """Repoint at a respawned worker (it lands on a fresh ephemeral
        port); pooled connections to the old incarnation are dropped."""
        with self._lock:
            self._addr = (addr[0], int(addr[1]))
            for s in self._pool:
                s.close()
            self._pool.clear()

    def _deadline(self, nbytes: int = 0) -> retry.Deadline:
        budget = self._timeout + self._per_mb * (nbytes / float(1 << 20))
        return retry.Deadline(retry.effective_budget(budget))

    def _conn(self, dl: retry.Deadline,
              gated: bool = True) -> socket.socket:
        if gated and self._breaker is not None \
                and not self._breaker.allow():
            e = WorkerError(
                f"worker breaker '{self._breaker.name}' open: "
                "skipping connect")
            e.breaker_open = True  # not evidence of a NEW failure
            raise e
        with self._lock:
            if self._pool:
                s = self._pool.pop()
                s.settimeout(dl.timeout())
                return s
        try:
            _M.incr("connect_attempts")
            s = socket.create_connection(self._addr, timeout=dl.timeout())
        except OSError as e:
            err = WorkerError(f"worker unreachable: {e}")
            if gated:
                # connect refusal is the clearest dead-worker evidence, and
                # it raises BEFORE the callers' try/except-_fail blocks —
                # record it here (ungated observability polls stay outside)
                self._fail(err)
            raise err from e
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def _release(self, s: socket.socket) -> None:
        with self._lock:
            if len(self._pool) < 8:
                self._pool.append(s)
                return
        s.close()

    def _ok(self) -> None:
        if self._breaker is not None:
            self._breaker.record_success()

    def _fail(self, e: BaseException) -> None:
        if self._breaker is not None \
                and not getattr(e, "breaker_open", False):
            self._breaker.record_failure()

    def _checked(self, resp: dict) -> dict:
        if "error" in resp:
            raise WorkerError(
                f"worker: {resp['error']}: {resp['message']}")
        return resp

    def _stamped(self, req: dict,
                 dl: "retry.Deadline | None" = None) -> dict:
        """Stamp the caller's span context (and remaining deadline budget)
        into the request frame (same contract as dt.send_op headers /
        RpcClient.call), so the worker's span nests under the DN pipeline
        span that drove it and its sub-calls inherit the budget."""
        tr = tracing.current_context()
        if tr is not None:
            req["_trace"] = list(tr)
        hdr = dl.header() if dl is not None else retry.remaining_header()
        if hdr is not None:
            req[retry.DEADLINE_KEY] = hdr
        return req

    def reduce_stream(self, packets, cdc: CdcConfig):
        """Forward an iterator of byte packets; returns (cuts, digests).
        This is the true streaming path: the DN calls it from inside its
        packet-receive loop, so client->DN->worker->HBM is one pipeline.

        The upload leg carries strides, not packets (the stride wire of
        proto/datatransfer.py).  An item of ``packets`` is ``(data, crc)``
        — bytes that arrived with a verified CRC32C, which is carried, not
        computed again — or plain bytes, summed here once in segments of at
        most ``_STRIDE``.  Whenever ``_STRIDE`` bytes are pending they
        leave as ONE frame in one ``sendmsg`` (phase ``worker_send``); the
        last frame takes what is left.  The deadline budget accrues
        ``deadline_s_per_mb`` per streamed MiB (payload size is only known
        as it arrives) and is checked once a frame.

        Exception classes: worker-side failures raise :class:`WorkerError`;
        anything the ``packets`` iterator itself raises (the caller's OWN
        stream — e.g. the DN's client connection dying) propagates
        unchanged, so the caller can tell the two apart."""
        dl = self._deadline()
        s = self._conn(dl)
        segs: list = []
        crcs: list[int] = []
        pending = 0

        def send(last: bool = False) -> None:
            nonlocal pending
            try:
                dl.extend(self._per_mb * pending / float(1 << 20))
                dl.check("worker reduce stream")
                s.settimeout(dl.timeout())
                with profiler.phase("worker_send"):
                    dt.write_stride(s, segs, crcs, last)
            except OSError as e:
                raise WorkerError(f"worker send failed: {e}") from e
            segs.clear()
            crcs.clear()
            pending = 0

        try:
            try:
                send_frame(s, self._stamped(
                    {"op": "reduce", "mask_bits": cdc.mask_bits,
                     "min_chunk": cdc.min_chunk,
                     "max_chunk": cdc.max_chunk}, dl))
            except OSError as e:
                raise WorkerError(f"worker send failed: {e}") from e
            # caller errors propagate UNWRAPPED out of this loop
            for part in packets:
                if isinstance(part, tuple):
                    cut = (part,)
                else:
                    view = memoryview(part)
                    cut = ((seg, native.crc32c(seg)) for seg in (
                        view[o:o + _STRIDE]
                        for o in range(0, len(view), _STRIDE)))
                for data, crc in cut:
                    if not len(data):
                        continue
                    segs.append(data)
                    crcs.append(crc)
                    pending += len(data)
                    if pending >= _STRIDE:
                        send()
            send(last=True)
            try:
                dl.check("worker reduce")
                s.settimeout(dl.timeout())
                # the final drain IS the wait on device compute: the worker
                # answers only after its TPU reduce completes, so the DN-side
                # timeline books it as device_wait (its own ledger records
                # nothing — the dispatches live in the worker process)
                with profiler.phase("device_wait"):
                    resp = self._checked(recv_frame(s))
            except (OSError, ConnectionError) as e:
                raise WorkerError(f"worker failed: {e}") from e
            cuts = np.frombuffer(resp["cuts"], np.int64)
            digs = np.frombuffer(resp["digests"],
                                 np.uint8).reshape(-1, 32)
            self._release(s)
            self._ok()
            return cuts, digs
        except BaseException as e:
            s.close()
            if isinstance(e, (WorkerError, retry.DeadlineExceeded)):
                self._fail(e)
            raise

    def reduce(self, data: bytes, cdc: CdcConfig):
        return self.reduce_stream([data], cdc)

    def _seal_round_trip(self, req: dict, datas: list, what: str) -> list:
        """One compress op: the request, ``datas`` up the hop in stride
        frames (phase ``seal_send``), then the reply — the compressed
        lengths in a frame and the payloads behind it, raw, each read into
        a buffer of its own (phase ``seal_wait``).

        Upload leg: every frame is up to ``_STRIDE`` bytes of ONE payload,
        as views of it — segments of ``dt.SEGMENT``, their CRC32Cs from
        one native call, one ``sendmsg``; nothing is sliced into a copy.
        An empty ``FLAG_LAST`` frame ends the stream."""
        dl = self._deadline(sum(len(d) for d in datas))
        s = self._conn(dl)
        try:
            try:
                with profiler.phase("seal_send"):
                    send_frame(s, self._stamped(req, dl))
                    dt.write_frames(s, [f for d in datas
                                        for f in dt.frames_of(d)])
                dl.check(what)
                s.settimeout(dl.timeout())
                with profiler.phase("seal_wait"):
                    sizes = self._checked(recv_frame(s))["sizes"]
                    outs = [dt.recv_payload(s, n) for n in sizes]
            except (OSError, ConnectionError) as e:
                raise WorkerError(f"worker failed: {e}") from e
            self._release(s)
            self._ok()
            return outs
        except BaseException as e:
            s.close()
            if isinstance(e, (WorkerError, retry.DeadlineExceeded)):
                self._fail(e)
            raise

    def compress(self, codec: str, data) -> bytearray:
        """``data`` (any bytes-like, read where it lies) through the
        worker's compressor."""
        (out,) = self._seal_round_trip(
            {"op": "compress", "codec": codec, "size": len(data)}, [data],
            "worker compress")
        return out

    def compress_batch(self, codec: str, datas: list) -> list:
        """Batched compress: one round trip, one worker-side device program
        for the group (see ReductionWorker._op_compress_batch)."""
        return self._seal_round_trip(
            {"op": "compress_batch", "codec": codec,
             "sizes": [len(d) for d in datas]}, datas,
            "worker compress_batch")

    def _poll(self, req: dict) -> dict:
        """One ungated request/response (observability polls stay outside
        the breaker, see the class docstring)."""
        s = self._conn(self._deadline(), gated=False)
        try:
            send_frame(s, req)
            out = self._checked(recv_frame(s))
            self._release(s)
            return out
        except BaseException:
            s.close()
            raise

    def ping(self) -> dict:
        return self._poll({"op": "ping"})

    def stats(self) -> dict:
        return self._poll({"op": "stats"})

    def device_report(self, probe: bool = False) -> dict:
        """ReductionWorker._device_report over the wire."""
        return self._poll({"op": "device_report", "probe": probe})

    def traces(self) -> dict:
        """Worker-process spans + device-ledger events (the DN proxies this
        through its own trace_spans op for the gateway merge)."""
        return self._poll({"op": "traces"})

    def close(self) -> None:
        with self._lock:
            for s in self._pool:
                s.close()
            self._pool.clear()


def spawn_local_worker(backend: str = "auto"):
    """Launch a worker as a real SEPARATE PROCESS (the co-located
    deployment shape); returns (Popen, (host, port)).  The caller owns the
    process (terminate() when done).  The worker's stderr is the caller's:
    a worker that cannot have its device says why where someone reads it."""
    import re
    import subprocess
    import sys

    proc = subprocess.Popen(
        [sys.executable, "-m", "hdrf_tpu.server.reduction_worker",
         "--port", "0", "--backend", backend],
        stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    m = re.search(r"listening on ([\d.]+):(\d+)", line)
    if not m:
        proc.terminate()
        rc = proc.wait()
        raise RuntimeError(
            f"worker failed to start (rc={rc}, stdout={line!r}; "
            "its stderr is above)")
    return proc, (m.group(1), int(m.group(2)))


def stop_local_worker(proc, grace_s: float = 20.0) -> None:
    """SIGTERM, then SIGKILL after ``grace_s``: a worker that holds a chip
    takes several seconds to die of SIGTERM (measured on the v5e host,
    PR 22: more than 5), and must be gone before its owner returns."""
    import subprocess

    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


class WorkerSupervisor:
    """Supervised co-located worker: owns the process, detects death, and
    respawns with capped full-jitter backoff (the NodeManager service-
    restart role the reference delegates to init systems; DataNode.java has
    no analog for its in-process codecs — they die with the daemon).

    ``on_respawn(addr)`` fires after each successful respawn so the owner
    repoints its :class:`WorkerClient` (`set_addr`) — respawned workers
    land on a fresh ephemeral port.  Clock/sleep/spawn are injectable so
    tests drive the respawn schedule without wall-clock waits.  A process
    that stayed up longer than ``healthy_s`` resets the backoff streak.
    """

    def __init__(self, backend: str = "auto", base_s: float = 0.5,
                 cap_s: float = 15.0, healthy_s: float = 30.0,
                 on_respawn=None, clock=time.monotonic,
                 sleep=time.sleep, spawn=spawn_local_worker,
                 poll_s: float = 0.2):
        self._backend = backend
        self._base_s = float(base_s)
        self._cap_s = float(cap_s)
        self._healthy_s = float(healthy_s)
        self._on_respawn = on_respawn
        self._clock = clock
        self._sleep = sleep
        self._spawn = spawn
        self._poll_s = float(poll_s)
        self._proc = None
        self.addr: tuple[str, int] | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._spawned_at = 0.0
        self._streak = 0  # consecutive quick deaths
        self.respawns = 0

    def start(self) -> tuple[str, int]:
        """Spawn the first incarnation and the monitor thread; returns the
        worker address (startup failures propagate to the caller — only
        RE-spawns are retried with backoff)."""
        self._proc, self.addr = self._spawn(self._backend)
        self._spawned_at = self._clock()
        self._thread = threading.Thread(target=self._monitor,
                                        name="worker-supervisor",
                                        daemon=True)
        self._thread.start()
        return self.addr

    def _monitor(self) -> None:
        import random as _random

        while not self._stop.is_set():
            if self._proc.poll() is None:
                self._sleep(self._poll_s)
                continue
            if self._stop.is_set():
                return
            if self._clock() - self._spawned_at >= self._healthy_s:
                self._streak = 0
            delay = _random.uniform(0.0, min(
                self._cap_s, self._base_s * (2.0 ** self._streak)))
            self._streak += 1
            _M.incr("worker_deaths")
            if delay > 0:
                self._sleep(delay)
            if self._stop.is_set():
                return
            try:
                self._proc, self.addr = self._spawn(self._backend)
            except Exception:
                _M.incr("worker_respawn_failures")
                continue  # next lap backs off further
            self._spawned_at = self._clock()
            self.respawns += 1
            _M.incr("worker_respawns")
            if self._on_respawn is not None:
                try:
                    self._on_respawn(self.addr)
                except Exception:
                    _M.incr("worker_respawn_callback_errors")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._proc is not None:
            stop_local_worker(self._proc)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="hdrf-reduction-worker")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--backend", default="auto")
    args = p.parse_args(argv)
    import sys

    from hdrf_tpu.utils import device_env

    if args.backend != "native":
        device_env.enable_compile_cache()
    if args.backend == "tpu":
        # Asked for the chip: anything else is a failure to start, said on
        # stderr, never a quiet run of the device programs on XLA:CPU.
        dev = device_env.device_info()
        if dev["platform"] != "tpu":
            print(f"reduction worker: --backend tpu but JAX reports "
                  f"platform {dev['platform']!r} ({dev['kind']}, "
                  f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
                  "refusing to start", file=sys.stderr)
            return 3
    w = ReductionWorker(args.host, args.port, backend=args.backend).start()
    # Startup banner goes to STDOUT (spawn_local_worker regex-parses the
    # "listening on host:port" substring off the first line — present in
    # both the text and JSON log formats).
    from hdrf_tpu.utils import log

    log.get_logger("reduction_worker", stream=sys.stdout).info(
        f"reduction worker ({w.backend}) listening on "
        f"{w.addr[0]}:{w.addr[1]}", backend=w.backend)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        w.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
