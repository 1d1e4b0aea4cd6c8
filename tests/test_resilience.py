"""Degraded-mode resilience spine: deadlines, retry budgets, circuit
breakers, worker failover (the fault matrix for utils/retry.py and the
paths rewired onto it — the reference's RetryPolicies.java:153 /
RetryInvocationHandler.java:88 behaviors the fork's reduction path lacked).

Every breaker/deadline state transition here is driven by INJECTED clocks
(the utils/outlier.py convention): no wall-clock sleeps gate an assertion.
The only time-bounded waits are heartbeat-propagation polls, which follow
the MiniCluster wait_for_* idiom.
"""

from __future__ import annotations

import random
import socket
import threading
import time

import numpy as np
import pytest

from hdrf_tpu.config import CdcConfig, NameNodeConfig
from hdrf_tpu.server.namenode import NameNode
from hdrf_tpu.testing.minicluster import MiniCluster
from hdrf_tpu.utils import fault_injection, metrics, retry

RNG = np.random.default_rng(77)


def _bytes(n):
    return RNG.integers(0, 256, size=n, dtype=np.uint8).tobytes()


class Boom(Exception):
    pass


@pytest.fixture(autouse=True)
def _fresh_breakers():
    retry.reset_breakers()
    yield
    retry.reset_breakers()
    fault_injection.clear()


# --------------------------------------------------------------- unit: budget


class TestDeadline:
    def test_fake_clock_lifecycle(self):
        t = [0.0]
        d = retry.Deadline(5.0, clock=lambda: t[0])
        assert d.remaining() == 5.0 and not d.expired
        t[0] = 4.0
        d.check("op")  # 1 s left: fine
        assert d.timeout() == pytest.approx(1.0)
        assert d.timeout(cap_s=0.25) == 0.25
        d.extend(2.0)  # budget accrual (streamed-MiB shape)
        t[0] = 6.5
        assert not d.expired
        t[0] = 7.0
        assert d.expired and d.remaining() == 0.0 and d.header() == 0.0
        with pytest.raises(retry.DeadlineExceeded):
            d.check("op")

    def test_ambient_bind_and_clamp(self):
        assert retry.current() is None
        assert retry.remaining_header() is None
        assert retry.effective_budget(60.0) == 60.0  # unclamped
        t = [0.0]
        with retry.bind(retry.Deadline(10.0, clock=lambda: t[0])) as d:
            assert retry.current() is d
            # local per-op budget may never outlive the end-to-end budget
            assert retry.effective_budget(60.0) == pytest.approx(10.0)
            assert retry.effective_budget(3.0) == 3.0
            assert retry.remaining_header() == pytest.approx(10.0)
        assert retry.current() is None

    def test_bind_remaining_rebinds_against_local_clock(self):
        t = [1000.0]  # a clock wildly different from the sender's
        with retry.bind_remaining(2.5, clock=lambda: t[0]) as d:
            assert d.remaining() == pytest.approx(2.5)
            t[0] = 1002.0
            assert d.remaining() == pytest.approx(0.5)
        with retry.bind_remaining(None) as d:
            assert d is None and retry.current() is None


class TestBackoffAndRetries:
    def test_full_jitter_bounds(self):
        delays = list(retry.backoff_delays(
            6, base_s=1.0, cap_s=4.0, rng=random.Random(7)))
        assert len(delays) == 6
        for i, d in enumerate(delays):
            assert 0.0 <= d <= min(4.0, 2.0 ** i)

    def test_call_with_retries_recovers(self):
        calls, slept = [], []
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("nope")
            return "ok"
        out = retry.call_with_retries(flaky, attempts=3,
                                      sleep=slept.append,
                                      rng=random.Random(1))
        assert out == "ok" and len(calls) == 3 and len(slept) == 2

    def test_exhausted_attempts_raise_last(self):
        def always():
            raise ConnectionError("down")
        with pytest.raises(ConnectionError, match="down"):
            retry.call_with_retries(always, attempts=2, sleep=lambda s: None)

    def test_spent_budget_short_circuits(self):
        t = [0.0]
        calls = []
        with retry.bind(retry.Deadline(0.0, clock=lambda: t[0])):
            with pytest.raises(retry.DeadlineExceeded):
                retry.call_with_retries(lambda: calls.append(1), attempts=3,
                                        sleep=lambda s: None)
        assert calls == []  # refused BEFORE running the op


# -------------------------------------------------------- unit: breaker state


class TestCircuitBreaker:
    def test_state_machine_with_injected_clock(self):
        t = [0.0]
        b = retry.CircuitBreaker("edge", failure_threshold=2, reset_s=10.0,
                                 clock=lambda: t[0])
        assert b.state == "closed" and b.allow()
        b.record_failure()
        assert b.state == "closed"  # 1 < threshold
        b.record_failure()
        assert b.state == "open" and not b.allow()
        with pytest.raises(retry.BreakerOpen):
            b.check()
        t[0] = 9.99
        assert b.state == "open"
        t[0] = 10.0
        assert b.state == "half_open"
        assert b.allow()       # THE probe
        assert not b.allow()   # only one probe admitted
        b.record_failure()     # probe failed -> straight back to open
        assert b.state == "open"
        t[0] = 20.0
        assert b.allow()       # half-open again, probe admitted
        b.record_success()
        assert b.state == "closed" and b.allow() and b.allow()

    def test_success_resets_consecutive_failures(self):
        b = retry.CircuitBreaker("edge2", failure_threshold=3)
        b.record_failure()
        b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state == "closed"  # streak broken: 2 < 3 consecutive

    def test_registry_is_per_edge_and_first_params_win(self):
        b1 = retry.breaker("dn-x->worker", failure_threshold=5)
        b2 = retry.breaker("dn-x->worker", failure_threshold=9)
        assert b1 is b2 and b1.failure_threshold == 5
        assert "dn-x->worker" in retry.all_breakers()
        m = metrics.registry("resilience").snapshot()["gauges"]
        assert m.get("breaker_state.dn-x->worker") == 0  # exported closed

    def test_transition_counters_exported(self):
        reg = metrics.registry("resilience")
        opened0 = reg.counter("breaker_open_total")
        closed0 = reg.counter("breaker_close_total")
        t = [0.0]
        b = retry.CircuitBreaker("edge3", failure_threshold=1, reset_s=1.0,
                                 clock=lambda: t[0])
        b.record_failure()
        t[0] = 1.0
        assert b.allow()
        b.record_success()
        assert reg.counter("breaker_open_total") == opened0 + 1
        assert reg.counter("breaker_close_total") == closed0 + 1


# ------------------------------------------------------------- rpc deadlines


class TestRpcDeadlines:
    def test_server_refuses_spent_budget_before_dispatch(self, tmp_path):
        from hdrf_tpu.proto.rpc import RpcClient, RpcError

        nn = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "n"))).start()
        try:
            c = RpcClient(nn.addr)
            # the rejection counter lives in the RPC layer's own registry
            # (rpc.py:90 — rpc.{name}), not the service's
            rejected0 = metrics.registry("rpc.namenode").counter(
                "mkdir_deadline_rejected")
            with pytest.raises(RpcError, match="DeadlineExceeded"):
                c.call("mkdir", path="/late", _deadline=0.0)
            assert metrics.registry("rpc.namenode").counter(
                "mkdir_deadline_rejected") == rejected0 + 1
            # the handler never ran
            assert not any(e["name"] == "late"
                           for e in nn.rpc_listing("/"))
            c.call("mkdir", path="/ok", _deadline=30.0)  # sane budget: runs
            assert any(e["name"] == "ok" for e in nn.rpc_listing("/"))
            c.close()
        finally:
            nn.stop()

    def test_client_refuses_spent_ambient_budget(self, tmp_path):
        from hdrf_tpu.proto.rpc import RpcClient

        nn = NameNode(NameNodeConfig(meta_dir=str(tmp_path / "n"))).start()
        try:
            c = RpcClient(nn.addr)
            t = [0.0]
            with retry.bind(retry.Deadline(0.0, clock=lambda: t[0])):
                with pytest.raises(retry.DeadlineExceeded):
                    c.call("mkdir", path="/never")
            assert not any(e["name"] == "never"
                           for e in nn.rpc_listing("/"))
            c.close()
        finally:
            nn.stop()


# ------------------------------------------------- hung worker: deadline caps


class _HangingServer:
    """Accepts connections and never responds (a wedged codec process)."""

    def __init__(self):
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.addr = self._srv.getsockname()
        self._conns: list[socket.socket] = []
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while True:
            try:
                c, _ = self._srv.accept()
            except OSError:
                return
            self._conns.append(c)

    def close(self):
        try:
            self._srv.close()
        except OSError:
            pass
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass


class TestHungWorkerDeadline:
    def test_client_unblocks_within_budget(self):
        """Satellite: the old hard-coded 600 s timeout is gone — a hung
        worker costs at most the configured payload-scaled budget."""
        from hdrf_tpu.server.reduction_worker import WorkerClient, WorkerError

        hang = _HangingServer()
        try:
            c = WorkerClient(hang.addr, deadline_s=0.6,
                             deadline_s_per_mb=0.0)
            t0 = time.monotonic()
            with pytest.raises((WorkerError, retry.DeadlineExceeded)):
                c.reduce(_bytes(200_000), CdcConfig())
            assert time.monotonic() - t0 < 30.0  # not 600 s
            c.close()
        finally:
            hang.close()

    def test_write_path_unblocks_and_degrades(self):
        """A DN pointed at a hung worker: the dedup write must complete via
        in-process passthrough within the deadline budget, not hang."""
        hang = _HangingServer()
        try:
            with MiniCluster(
                    n_datanodes=1, replication=1, block_size=1 << 20,
                    reduction_overrides={
                        "worker_addr": list(hang.addr),
                        "worker_deadline_s": 0.6,
                        "worker_deadline_s_per_mb": 0.0,
                        # keep the breaker out of THIS test's way
                        "worker_breaker_failures": 100}) as mc:
                br = metrics.registry("block_receiver")
                fallbacks0 = br.counter("worker_fallbacks")
                degraded0 = br.counter("degraded_writes")
                data = _bytes(400_000)
                t0 = time.monotonic()
                with mc.client("hung") as c:
                    c.write("/hung/f", data, scheme="dedup_lz4")
                    assert c.read("/hung/f") == data
                assert time.monotonic() - t0 < 60.0
                assert br.counter("worker_fallbacks") > fallbacks0
                assert br.counter("degraded_writes") > degraded0
        finally:
            hang.close()


# ------------------------------------- acceptance: kill -9 / breaker / probe


class TestWorkerFailover:
    def test_kill9_breaker_opens_then_halfopen_recovers(self):
        """The fault matrix end to end: kill -9 the reduction worker
        mid-write -> the write completes via passthrough with zero data
        loss; the breaker opens after the configured failure count and
        subsequent writes make NO worker connect attempts; restarting the
        worker and advancing the breaker's injected clock past reset_s
        re-admits the edge (half-open probe -> closed, reduction back on).
        """
        br = metrics.registry("block_receiver")
        wm = metrics.registry("reduction_worker")
        with MiniCluster(
                n_datanodes=1, replication=1, block_size=1 << 20,
                tpu_worker=True,
                reduction_overrides={
                    "worker_deadline_s": 20.0,
                    "worker_breaker_failures": 2,
                    # effectively never on the wall clock; the test drives
                    # half-open by moving the breaker's injected clock
                    "worker_breaker_reset_s": 3600.0}) as mc:
            dn = mc.datanodes[0]
            breaker = dn._worker_breaker
            assert breaker is not None and breaker.state == "closed"

            # --- healthy baseline: the worker serves the reduce
            reduces0 = br.counter("worker_reduces")
            a = _bytes(400_000)
            with mc.client("fo") as c:
                c.write("/fo/a", a, scheme="dedup_lz4")
                assert c.read("/fo/a") == a
            assert br.counter("worker_reduces") == reduces0 + 1

            # --- kill -9 MID-WRITE: first packet of the next block
            fired = threading.Event()

            def kill_once(**kw):
                if not fired.is_set():
                    fired.set()
                    mc.kill_worker()

            b = _bytes(400_000)
            fallbacks0 = br.counter("worker_fallbacks")
            degraded0 = br.counter("degraded_writes")
            with fault_injection.inject("block_receiver.packet", kill_once):
                with mc.client("fo") as c:
                    c.write("/fo/b", b, scheme="dedup_lz4")
                    assert c.read("/fo/b") == b  # zero data loss
            assert fired.is_set()
            assert br.counter("worker_fallbacks") == fallbacks0 + 1
            assert br.counter("degraded_writes") == degraded0 + 1
            assert breaker.state == "closed"  # 1 failure < threshold 2

            # --- second failure (connect refused): breaker opens
            c2 = _bytes(300_000)
            with mc.client("fo") as c:
                c.write("/fo/c", c2, scheme="dedup_lz4")
                assert c.read("/fo/c") == c2
            assert breaker.state == "open"
            assert dn.reduction_degraded

            # --- open breaker: degraded writes make ZERO connect attempts
            attempts0 = wm.counter("connect_attempts")
            d = _bytes(300_000)
            with mc.client("fo") as c:
                c.write("/fo/d", d, scheme="dedup_lz4")
                assert c.read("/fo/d") == d
            assert wm.counter("connect_attempts") == attempts0
            assert metrics.registry("resilience").snapshot()["gauges"][
                f"breaker_state.{breaker.name}"] == 2  # open, exported

            # --- degradation reaches the NN within a couple of heartbeats
            with mc.client("fo") as c:
                deadline = time.monotonic() + 10.0
                cs = {}
                while time.monotonic() < deadline:
                    cs = c._nn.call("cluster_status")
                    if cs.get("reduction_degraded"):
                        break
                    time.sleep(0.05)
                assert cs.get("reduction_degraded") == 1
                assert cs.get("degraded_nodes") == [dn.dn_id]

            # --- restart the worker; drive half-open by the injected clock
            mc.restart_worker()
            breaker._opened_at = breaker._clock() - breaker.reset_s - 1.0
            assert breaker.state == "half_open"
            reduces1 = br.counter("worker_reduces")
            e = _bytes(300_000)
            with mc.client("fo") as c:
                c.write("/fo/e", e, scheme="dedup_lz4")  # the probe
                assert c.read("/fo/e") == e
            assert breaker.state == "closed"  # probe succeeded: re-closed
            assert br.counter("worker_reduces") == reduces1 + 1
            assert not dn.reduction_degraded

            # earlier degraded files still read back intact
            with mc.client("fo") as c:
                assert c.read("/fo/b") == b
                assert c.read("/fo/c") == c2


class TestAlteredStride:
    @pytest.mark.parametrize("damage", ["payload", "crc"])
    def test_the_worker_refuses_and_the_datanode_falls_back(
            self, monkeypatch, damage):
        """A byte, or a carried CRC, altered between the DataNode and the
        worker (the stride wire carries the client's own CRCs): the worker
        answers an error, the block is reduced in-process and reads back
        identical — what a killed worker gets, from the same counters."""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.server.reduction_worker import ReductionWorker

        br = metrics.registry("block_receiver")
        wm = metrics.registry("reduction_worker")
        real, hit = dt.write_stride, []

        def altered(sock, segs, crcs, last=False):
            if segs and not hit:
                hit.append(len(segs))
                if damage == "payload":
                    bad = bytearray(segs[1])
                    bad[7] ^= 0x40
                    segs = [segs[0], bytes(bad), *segs[2:]]
                else:
                    crcs = [crcs[0], crcs[1] ^ 1, *crcs[2:]]
            real(sock, segs, crcs, last)

        w = ReductionWorker(backend="native").start()
        try:
            with MiniCluster(
                    n_datanodes=1, replication=1, block_size=1 << 20,
                    reduction_overrides={
                        "worker_addr": list(w.addr),
                        "worker_breaker_failures": 100}) as mc:
                good, data = _bytes(300_000), _bytes(400_000)
                with mc.client("alt") as c:
                    c.write("/alt/good", good, scheme="dedup_lz4")
                    before = (br.counter("worker_fallbacks"),
                              br.counter("degraded_writes"),
                              wm.counter("op_errors"),
                              br.counter("worker_reduces"))
                    monkeypatch.setattr(dt, "write_stride", altered)
                    c.write("/alt/f", data, scheme="dedup_lz4")
                    assert c.read("/alt/f") == data
                    assert hit == [7]           # 400 000 bytes: 7 packets
                    assert (br.counter("worker_fallbacks"),
                            br.counter("degraded_writes"),
                            wm.counter("op_errors"),
                            br.counter("worker_reduces")) == (
                        before[0] + 1, before[1] + 1, before[2] + 1,
                        before[3])
                    # the wire is sound again: the next block is the worker's
                    c.write("/alt/g", data[::-1], scheme="dedup_lz4")
                    assert c.read("/alt/g") == data[::-1]
                    assert br.counter("worker_reduces") == before[3] + 1
                    assert c.read("/alt/good") == good
        finally:
            w.stop()


# ------------------------------------- runs, not packets, and today's wire


class TestRunsOnTheWire:
    """``receive_reduced`` takes the client stream a run at a time (PR 28)
    and the wire stays a packet at a time: one ack a packet, in order, none
    before its packet's verify and fault point, the last after the commit —
    through each branch that consumes the runs and the encrypted socket."""

    BRANCHES = {
        "in-process": {},       # no worker: drained and reduced here
        "worker": {},           # worker_addr filled in by the fixture
        # a worker_addr that refuses every connection, the breaker held
        # shut: each block goes through the worker_down arm
        "degraded": {},
        "encrypted": {"secure": True},
    }
    PKT = 4096

    @pytest.fixture(scope="class", params=sorted(BRANCHES))
    def node(self, request):
        from hdrf_tpu.server.reduction_worker import ReductionWorker

        kw = dict(self.BRANCHES[request.param])
        w = refuser = None
        if request.param == "worker":
            w = ReductionWorker(backend="native").start()
            kw["reduction_overrides"] = {"worker_addr": list(w.addr)}
        elif request.param == "degraded":
            # bound and never listening: connects are refused, and the
            # port is nobody else's while the class runs
            refuser = socket.socket()
            refuser.bind(("127.0.0.1", 0))
            kw["reduction_overrides"] = {
                "worker_addr": list(refuser.getsockname()),
                "worker_breaker_failures": 100}
        try:
            with MiniCluster(n_datanodes=1, replication=1,
                             block_size=1 << 20, **kw) as mc:
                yield request.param, mc
        finally:
            if w is not None:
                w.stop()
            if refuser is not None:
                refuser.close()

    @staticmethod
    def _open(node, path):
        """A client's WRITE_BLOCK op on a raw socket: (socket, block id)."""
        from hdrf_tpu.testing.wire import open_write_block

        branch, mc = node
        return open_write_block(mc, path, encrypted=branch == "encrypted")

    @staticmethod
    def _acks_until_closed(s) -> list:
        from hdrf_tpu.proto import datatransfer as dt

        acks = []
        try:
            while True:
                acks.append(dt.read_ack(s))
        except (ConnectionError, OSError):
            return acks

    @pytest.mark.parametrize("window", [1, 16])
    def test_a_window_completes_and_the_last_ack_follows_the_commit(
            self, node, window):
        """A sender that waits for each ack before its next packet
        (runs of one) and one with the default window both finish; the
        last ack is not on the wire until the commit has returned."""
        from hdrf_tpu.proto import datatransfer as dt

        br = metrics.registry("block_receiver")
        data = _bytes(300_000)
        packets = -(-len(data) // self.PKT) + 1
        committed = []

        def slow_commit(**kw):
            time.sleep(0.2)
            committed.append(time.monotonic())

        before = (br.counter("recv_packets"), br.counter("recv_runs"),
                  br.counter("degraded_writes"))
        s, bid = self._open(node, f"/runs/w{window}")
        try:
            with fault_injection.inject("dedup.container_append",
                                        slow_commit):
                seqno, status = dt.stream_bytes_acked(s, data, self.PKT,
                                                      window)
                done = time.monotonic()
        finally:
            s.close()
        assert (seqno, status) == (packets - 1, dt.ACK_SUCCESS)
        assert len(committed) == 1 and done >= committed[0]
        got = (br.counter("recv_packets") - before[0],
               br.counter("recv_runs") - before[1])
        assert got[0] == packets and 1 <= got[1] <= packets
        if window == 1:         # the DataNode never saw two at once
            assert got[1] == packets
        # the refused worker, and no other branch, took the worker_down arm
        assert (br.counter("degraded_writes") - before[2]
                == (node[0] == "degraded"))
        dn = node[1].datanodes[0]
        assert dn.replicas.get_meta(bid).logical_len == len(data)
        assert bytes(dn._sender.read_logical(bid)) == data

    @pytest.mark.parametrize("k", [0, 5])
    def test_a_corrupted_packet_is_not_acked_nor_any_after_it(self, node, k):
        """Packets 0..``k`` in one write, the last of them altered: acks
        for the packets before ``k`` and for no other, then the DataNode
        hangs up, and nothing is stored.  (Nothing is sent after ``k``: a
        close over unread bytes is a reset, which may drop the acks on
        their way.)"""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.testing.wire import frame_packets

        wire = bytearray(frame_packets((i, _bytes(1000), 0)
                                       for i in range(k + 1)))
        wire[-1000 - 1] ^= 1        # the last header's CRC, its top byte
        s, bid = self._open(node, f"/runs/bad{k}")
        try:
            s.sendall(wire)
            acks = self._acks_until_closed(s)
        finally:
            s.close()
        assert acks == [(i, dt.ACK_SUCCESS) for i in range(k)]
        assert node[1].datanodes[0].replicas.get_meta(bid) is None

    def test_a_raising_fault_point_aborts_before_its_ack(self, node):
        """Packets 0-2 sent and acked one by one, then 3-7 and the last at
        once; a handler that raises at seqno 3 ends the write: no ack for
        3 or after, though all of them had arrived and verify."""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.testing.wire import frame_packets

        s, bid = self._open(node, "/runs/boom")

        def boom(**kw):
            if kw["block_id"] == bid and kw["seqno"] == 3:
                raise IOError("injected at seqno 3")

        rest = frame_packets([(i, _bytes(1000), 0) for i in range(3, 8)]
                             + [(8, b"", dt.FLAG_LAST)])
        try:
            with fault_injection.inject("block_receiver.packet", boom):
                for i in range(3):
                    dt.write_packet(s, i, _bytes(1000))
                    assert dt.read_ack(s) == (i, dt.ACK_SUCCESS)
                s.sendall(rest)
                assert self._acks_until_closed(s) == []
        finally:
            s.close()
        assert node[1].datanodes[0].replicas.get_meta(bid) is None


# ----------------------------------------- mirror failures reach the NN view


class TestMirrorFailureReporting:
    def test_broken_mirror_flagged_within_two_heartbeats(self):
        """Satellite: a mirror push that breaks outright rides the NEXT
        heartbeat as per-peer ``mirror_failures`` and the NN flags the peer
        in slow_peers with rule=mirror_failure — broken beats slow."""
        with MiniCluster(n_datanodes=2, replication=2,
                         block_size=1 << 20) as mc:
            data = _bytes(300_000)
            # only the mirror leg uses op "write_reduced" (client writes use
            # WRITE_BLOCK), so this breaks exactly the mirror ingest —
            # whichever DN the NN picked as the pipeline head
            with fault_injection.inject(
                    "datanode.op",
                    lambda **kw: ((_ for _ in ()).throw(Boom())
                                  if kw.get("op") == "write_reduced"
                                  else None)):
                with mc.client("mf") as c:
                    c.write("/mf/f", data, scheme="dedup_lz4")
                    assert c.read("/mf/f") == data  # primary replica serves
            flagged = {peer: n for dn in mc.datanodes if dn is not None
                       for peer, n in dn._mirror_fail.items()}
            assert flagged, "primary never attributed the broken mirror"
            with mc.client("mf") as c:
                deadline = time.monotonic() + 10.0
                health = {}
                while time.monotonic() < deadline:
                    health = c._nn.call("slow_nodes_report")
                    if health.get("mirror_failures"):
                        break
                    time.sleep(0.05)
                assert health.get("mirror_failures"), \
                    "mirror failure never reached the NN health report"
                for peer, n in health["mirror_failures"].items():
                    assert peer in flagged and n >= 1
                    assert peer in health["slow_peers"]
                    assert health["slow_peers"][peer][
                        "mirror_failures"] >= 1


# --------------------------------------------------- crash-ordering matrices


def h(i: int) -> bytes:
    return bytes([i]) * 32


class TestIndexCrashOrdering:
    def test_wal_append_crash_leaves_memory_untouched(self, tmp_path):
        """Log-before-apply: a failed WAL append must not mutate memory, and
        the retried commit must land EXACTLY once (refcount == 1)."""
        from hdrf_tpu.index.chunk_index import ChunkIndex

        idx = ChunkIndex(str(tmp_path))
        with fault_injection.inject(
                "index.wal_append",
                lambda **kw: (_ for _ in ()).throw(OSError("disk full"))):
            with pytest.raises(OSError, match="disk full"):
                idx.commit_block(1, 10, [h(1)], {h(1): (0, 0, 10)})
        assert not idx.has_block(1)
        assert idx.chunk_location(h(1)) is None
        idx.commit_block(1, 10, [h(1)], {h(1): (0, 0, 10)})  # retry
        assert idx.chunk_location(h(1)).refcount == 1  # not double-applied
        idx.close()
        idx2 = ChunkIndex(str(tmp_path))  # crash-restart replay agrees
        assert idx2.chunk_location(h(1)).refcount == 1
        idx2.close()

    def test_wal_append_crash_preserves_prior_blocks(self, tmp_path):
        from hdrf_tpu.index.chunk_index import ChunkIndex

        idx = ChunkIndex(str(tmp_path))
        idx.commit_block(1, 10, [h(1)], {h(1): (0, 0, 10)})
        with fault_injection.inject(
                "index.wal_append",
                lambda **kw: (_ for _ in ()).throw(Boom())):
            with pytest.raises(Boom):
                idx.commit_block(2, 20, [h(2)], {h(2): (0, 10, 20)})
        idx.close()  # simulate death; reopen from WAL
        idx2 = ChunkIndex(str(tmp_path))
        assert idx2.has_block(1) and not idx2.has_block(2)  # no lost chunks
        assert idx2.chunk_location(h(1)).refcount == 1
        idx2.commit_block(2, 20, [h(2)], {h(2): (0, 10, 20)})
        assert idx2.chunk_location(h(2)).refcount == 1
        idx2.close()

    def test_auto_checkpoint_post_crash_no_double_apply(self, tmp_path):
        """Crash at the AUTO-triggered checkpoint's post_checkpoint window
        (publish done, WAL truncation lost): seqno filtering must keep
        replay idempotent — refcounts exact, nothing lost."""
        from hdrf_tpu.index.chunk_index import ChunkIndex

        idx = ChunkIndex(str(tmp_path), checkpoint_every=2)
        idx.commit_block(1, 10, [h(1)], {h(1): (0, 0, 10)})
        with fault_injection.inject(
                "index.post_checkpoint",
                lambda **kw: (_ for _ in ()).throw(Boom())):
            with pytest.raises(Boom):
                # 2nd commit trips the every-2 checkpoint; the record itself
                # was logged AND applied before the checkpoint crashed
                idx.commit_block(2, 20, [h(2)], {h(2): (0, 10, 20)})
        idx.close()
        idx2 = ChunkIndex(str(tmp_path))
        assert idx2.chunk_location(h(1)).refcount == 1  # not inflated
        assert idx2.chunk_location(h(2)).refcount == 1
        assert idx2.delete_block(1) == [h(1)]
        assert idx2.delete_block(2) == [h(2)]
        idx2.close()

    def test_torn_final_wal_record_dropped_after_checkpoint(self, tmp_path):
        """Checkpoint + intact WAL records + a TORN final record: recovery
        keeps everything up to the tear and drops only the torn tail."""
        from hdrf_tpu.index.chunk_index import ChunkIndex

        idx = ChunkIndex(str(tmp_path))
        idx.commit_block(1, 10, [h(1)], {h(1): (0, 0, 10)})
        idx.checkpoint()
        idx.commit_block(2, 20, [h(2)], {h(2): (0, 10, 20)})
        idx.commit_block(3, 30, [h(3)], {h(3): (0, 30, 30)})
        idx.close()
        wal = tmp_path / "index.wal"
        wal.write_bytes(wal.read_bytes()[:-3])  # crash mid-append of blk 3
        idx2 = ChunkIndex(str(tmp_path))
        assert idx2.has_block(1) and idx2.has_block(2)
        assert not idx2.has_block(3)  # torn record dropped, not corrupted
        assert idx2.chunk_location(h(1)).refcount == 1
        assert idx2.chunk_location(h(2)).refcount == 1
        idx2.commit_block(3, 30, [h(3)], {h(3): (0, 30, 30)})  # log continues
        assert idx2.has_block(3)
        idx2.close()


class TestDaemonLoopFaults:
    def test_namenode_monitor_survives_injected_fault(self):
        """The supervision loops are themselves resilient: a raising
        monitor tick is accounted (monitor_errors) and the NEXT tick runs —
        dead-node detection keeps working after the fault clears."""
        with MiniCluster(n_datanodes=1, replication=1, heartbeat_s=0.1,
                         dead_node_s=0.6) as mc:
            errors0 = metrics.registry("namenode").counter("monitor_errors")
            ticks = threading.Event()

            def boom(**kw):
                ticks.set()
                raise Boom()

            with fault_injection.inject("namenode.monitor_tick", boom):
                assert ticks.wait(5.0), "monitor never ticked"
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline and metrics.registry(
                        "namenode").counter("monitor_errors") <= errors0:
                    time.sleep(0.02)
            assert metrics.registry("namenode").counter(
                "monitor_errors") > errors0
            mc.kill_datanode(0)  # post-fault: the loop still declares death
            with mc.client("mt") as c:
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if all(not d["alive"] for d in c.datanode_report()):
                        break
                    time.sleep(0.05)
                assert all(not d["alive"] for d in c.datanode_report())

    def test_one_journalnode_append_fault_quorum_survives(self):
        """A single JN append failure must not fail the edit: 2/3 acks."""
        fired = threading.Event()

        def crash_once(**kw):
            if not fired.is_set():
                fired.set()
                raise OSError("jn disk error")

        with MiniCluster(n_datanodes=1, replication=1,
                         journal_nodes=3) as mc:
            with fault_injection.inject("journalnode.append", crash_once):
                with mc.client("jn") as c:
                    c.mkdir("/jn/survives")
                    assert any(e["name"] == "survives"
                               for e in c.ls("/jn"))
            assert fired.is_set()

    def test_replica_finalize_crash_client_retries(self):
        """Crash in the finalize window (data fsync'd, meta not yet
        written): the pipeline aborts and the client's block-granular
        retry lands the write — zero data loss on read-back."""
        fired = threading.Event()

        def crash_once(**kw):
            if not fired.is_set():
                fired.set()
                raise Boom()

        data = _bytes(200_000)
        with MiniCluster(n_datanodes=2, replication=1) as mc:
            with fault_injection.inject("replica.finalize", crash_once):
                with mc.client("rf") as c:
                    c.write("/rf/f", data, scheme="direct")
                    assert c.read("/rf/f") == data
            assert fired.is_set()


class TestContainerSealCrash:
    def test_seal_crash_loses_no_chunks(self, tmp_path):
        """Crash inside seal (before the sealed file is published): the raw
        container must survive, every chunk stays readable, and a retried
        seal completes."""
        import os

        from hdrf_tpu.storage.container_store import ContainerStore

        store = ContainerStore(str(tmp_path), container_size=1 << 20,
                               lanes=1)
        chunks = [_bytes(3000) for _ in range(5)]
        locs = store.append_chunks(chunks)
        cid = locs[0][0]
        with fault_injection.inject(
                "container.seal",
                lambda **kw: (_ for _ in ()).throw(Boom())):
            with pytest.raises(Boom):
                store.seal(cid)
        assert os.path.exists(store._raw_path(cid))      # raw survived
        assert not os.path.exists(store._sealed_path(cid))
        got = store.read_chunks(locs)
        assert got == chunks                             # no lost chunks
        store.seal(cid)                                  # retry completes
        assert os.path.exists(store._sealed_path(cid))
        assert store.read_chunks(locs) == chunks         # and still serves
