"""Overload-safe traffic plane (ISSUE 14 / ARCHITECTURE.md design
decision 14): per-tenant token-bucket admission, weighted-fair dequeue,
deadline-aware load shedding, and k+δ straggler-proof EC stripe reads.

Covers utils/qos.py (TenantBucket deficit math, AdmissionController
bucket/deadline sheds, FairQueue round-robin + close-sentinel contract),
the admission wiring through server/block_receiver.py and
server/read_plane.py (including the semaphore permit-leak regressions),
the ShedError wire round-trip (proto/datatransfer.py ACK_SHED, error
frames), the noisy-neighbor acceptance matrix on a two-tenant
MiniCluster, and the hedged stripe gather of server/ec_tier.py.
Exercises the fault points "qos.admit", "qos.shed" and
"ec.stripe_hedge".
"""

import threading
import time
from queue import Empty

import numpy as np
import pytest

from hdrf_tpu.utils import fault_injection, metrics, qos, retry

_QOS = metrics.registry("qos")
_EC = metrics.registry("ec")


@pytest.fixture(autouse=True)
def _clear_faults():
    fault_injection.clear()
    yield
    fault_injection.clear()


def _wait(pred, timeout=20.0, interval=0.1, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


# ------------------------------------------------------ deficit buckets


class TestTenantBucket:
    def test_deficit_and_refill(self):
        clk = _FakeClock()
        b = qos.TenantBucket(rate_bytes_s=100.0, burst_bytes=50.0,
                             clock=clk)
        assert b.try_admit() == 0.0
        # charge AFTER the op may overdraw: 250 bytes against a 50 burst
        b.charge(250)
        assert b.level == pytest.approx(-200.0)
        # retry-after = time for the level to climb back past zero
        assert b.try_admit() == pytest.approx(2.0)
        clk.t += 1.0
        assert b.try_admit() == pytest.approx(1.0)
        clk.t += 1.5
        assert b.try_admit() == 0.0
        # refill clamps at the burst, not unbounded credit
        clk.t += 100.0
        assert b.level == pytest.approx(50.0)

    def test_zero_rate_is_unlimited_until_configured(self):
        ctrl = qos.AdmissionController(rate_mb_s=0.0)
        for _ in range(50):
            ctrl.admit("anyone", "write")
            ctrl.charge("anyone", "write", 1 << 30)


# --------------------------------------------------- weighted-fair queue


class _Item:
    __slots__ = ("tenant", "tag")

    def __init__(self, tenant, tag=0):
        self.tenant = tenant
        self.tag = tag


class TestFairQueue:
    def test_round_robin_interleaves_flood_and_light(self):
        """64 queued items from a flooding tenant must not delay a light
        tenant's 8: round-robin serves one per tenant per cycle, so all
        of B's items land within the first 2*8 dequeues."""
        q = qos.FairQueue()
        for i in range(64):
            q.put(_Item("flood", i))
        for i in range(8):
            q.put(_Item("light", i))
        first = [q.get_nowait() for _ in range(16)]
        assert sum(1 for it in first if it.tenant == "light") == 8
        # and within each lane, FIFO order is preserved
        light_tags = [it.tag for it in first if it.tenant == "light"]
        assert light_tags == sorted(light_tags)

    def test_close_sentinel_served_after_data_drains(self):
        """The pipelines' ``None`` close sentinel parks in the control
        lane: queued work drains first, preserving the close contract."""
        q = qos.FairQueue()
        q.put(_Item("a"))
        q.put(None)
        q.put(_Item("b"))
        got = [q.get_nowait() for _ in range(3)]
        assert got[-1] is None
        assert {it.tenant for it in got[:2]} == {"a", "b"}
        with pytest.raises(Empty):
            q.get_nowait()

    def test_blocking_get_wakes_on_put(self):
        q = qos.FairQueue()
        out = []
        t = threading.Thread(target=lambda: out.append(q.get(timeout=5.0)))
        t.start()
        time.sleep(0.05)
        q.put(_Item("x"))
        t.join(timeout=5.0)
        assert out and out[0].tenant == "x"
        with pytest.raises(Empty):
            q.get(timeout=0.01)

    def test_depth_by_tenant(self):
        q = qos.FairQueue()
        for _ in range(3):
            q.put(_Item("a"))
        q.put(_Item("b"))
        assert q.depth_by_tenant() == {"a": 3, "b": 1}
        assert q.qsize() == 4


# ------------------------------------------------- admission controller


class TestAdmissionController:
    def test_bucket_shed_carries_retry_after_and_isolates_tenants(self):
        clk = _FakeClock()
        ctrl = qos.AdmissionController(rate_mb_s=1.0, burst_mb=1.0,
                                       clock=clk)
        admits, sheds = [], []
        with fault_injection.inject(
                "qos.admit", lambda **kw: admits.append(kw)), \
                fault_injection.inject(
                    "qos.shed", lambda **kw: sheds.append(kw)):
            ctrl.admit("hog", "write")
            ctrl.charge("hog", "write", 5 << 20)  # 5x the burst
            with pytest.raises(qos.ShedError) as ei:
                ctrl.admit("hog", "write")
            # retry-after = the 4 MiB deficit at 1 MiB/s
            assert ei.value.retry_after_s == pytest.approx(4.0)
            assert ei.value.tenant == "hog" and ei.value.op == "write"
            # the light tenant's bucket is untouched by the hog's deficit
            ctrl.admit("light", "write")
            # the bucket refills with time and the hog re-admits
            clk.t += 5.0
            ctrl.admit("hog", "write")
        assert [s["tenant"] for s in sheds] == ["hog"]
        assert sheds[0]["why"] == "rate"
        assert len(admits) == 4  # every admission attempt fires the point
        assert ctrl.report()["tenant_sheds"] == {"hog": 1}
        assert ctrl.sheds_total() == 1

    def test_deadline_shed_requires_warmed_estimator(self):
        """A cold service-time window must never shed; once >=5 samples
        land, a deadline that cannot cover p95 * shed_p95_mult is
        refused at admission with the needed budget as the hint."""
        clk = _FakeClock()
        ctrl = qos.AdmissionController(shed_p95_mult=3.0, clock=clk)
        short = retry.Deadline(0.05)
        # cold estimator: admitted even with a microscopic budget
        ctrl.admit("t", "read", deadline=short)
        for _ in range(6):
            ctrl.note_latency("read", 0.2)
        with pytest.raises(qos.ShedError) as ei:
            ctrl.admit("t", "read", deadline=retry.Deadline(0.05))
        assert ei.value.retry_after_s == pytest.approx(0.6, rel=0.2)
        # a budget that covers the estimate passes
        ctrl.admit("t", "read", deadline=retry.Deadline(5.0))
        # ops are estimated independently: writes have no samples
        ctrl.admit("t", "write", deadline=retry.Deadline(0.05))

    def test_ambient_deadline_is_picked_up(self):
        ctrl = qos.AdmissionController()
        for _ in range(6):
            ctrl.note_latency("write", 0.5)
        with retry.bind(retry.Deadline(0.01)):
            with pytest.raises(qos.ShedError):
                ctrl.admit("t", "write")
        ctrl.admit("t", "write")  # no ambient deadline -> no shed


# ------------------------------------------- permit-leak regressions


class TestPermitLeaks:
    def _shedding_ctrl(self):
        ctrl = qos.AdmissionController(rate_mb_s=1.0, burst_mb=1.0)
        ctrl.admit("hog", "write")
        ctrl.charge("hog", "write", 1 << 40)  # bucket never recovers
        return ctrl

    @staticmethod
    def _write_block(mc, path: str, tenant, payload: bytes) -> list:
        """One WRITE_BLOCK op on a raw socket — ``payload`` in one packet
        and the empty last one — and every ack the DataNode answered
        before it hung up or the last ack came."""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.testing.wire import open_write_block

        s, _bid = open_write_block(mc, path, tenant)
        acks = []
        with s:
            dt.write_packet(s, 0, payload)
            dt.write_packet(s, 1, b"", last=True)
            try:
                while len(acks) < 2:
                    acks.append(dt.read_ack(s))
            except (ConnectionError, OSError):
                pass
        return acks

    @staticmethod
    def _spend(dn, *tenants) -> None:
        for t in tenants:
            dn.qos.admit(t, "write")
            dn.qos.charge(t, "write", 1 << 40)  # bucket never recovers

    def test_shed_write_blocks_hold_no_write_slot(self):
        """100 WRITE_BLOCK ops of an over-rate tenant are answered
        ``ACK_SHED`` packet for packet, carry a retry-after hint, and
        take no ``DataNode.write_slot()``: a light tenant's write then
        completes and every slot is free again (the flood cannot starve
        the gate that remains by leaking its semaphore)."""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.testing.minicluster import MiniCluster

        br = metrics.registry("block_receiver")
        data = np.random.default_rng(29).integers(
            0, 256, size=64 * 1024, dtype=np.uint8).tobytes()
        with MiniCluster(n_datanodes=1, replication=1, block_size=1 << 20,
                         reduction_overrides={
                             "qos_tenant_rate_mb_s": 1.0,
                             "qos_tenant_burst_mb": 1.0}) as mc:
            dn = mc.datanodes[0]
            self._spend(dn, "hog")
            free = dn._write_sem._value
            sheds0 = br.counter("write_sheds")
            for i in range(100):
                acks = self._write_block(mc, f"/shed/{i}", "hog", data)
                assert [st for _, st in acks] == [dt.ACK_SHED] * 2
                assert all(hint_ms > 0 for hint_ms, _ in acks)
            assert dn.await_xceivers()
            assert br.counter("write_sheds") - sheds0 == 100
            assert dn._write_sem._value == free
            with mc.client("light") as c:
                c.write("/shed/light", data, scheme="dedup_lz4")
                assert c.read("/shed/light") == data
            assert dn._write_sem._value == free

    def test_read_coalescer_sheds_and_failures_leak_no_permits(self):
        from hdrf_tpu.server.read_plane import ReadCoalescer

        class _Containers:
            def read_chunks(self, locs, decompress_batch=None):
                raise IOError("injected container read failure")

        ctrl = self._shedding_ctrl()
        rc = ReadCoalescer(_Containers(), max_inflight=4, backend="native",
                           qos_ctrl=ctrl)
        before = rc._sem._value
        for _ in range(100):
            with pytest.raises(qos.ShedError):
                rc.fetch([(1, 0, 8)], tenant="hog")
        # admitted tenant: the decode failure path releases via finally
        for _ in range(100):
            with pytest.raises(IOError):
                rc.fetch([(1, 0, 8)], tenant="light")
        assert rc._sem._value == before

    def test_unattributed_write_block_is_never_shed(self):
        """Internal relays (mirror ingest, scrub, EC fan-in) carry no
        tenant and bypass admission — a WRITE_BLOCK with no ``_client``
        commits though every bucket, the default lane's too, is spent."""
        from hdrf_tpu.proto import datatransfer as dt
        from hdrf_tpu.testing.minicluster import MiniCluster

        br = metrics.registry("block_receiver")
        with MiniCluster(n_datanodes=1, replication=1, block_size=1 << 20,
                         reduction_overrides={
                             "qos_tenant_rate_mb_s": 1.0,
                             "qos_tenant_burst_mb": 1.0}) as mc:
            dn = mc.datanodes[0]
            self._spend(dn, "hog", "anon", "raw")
            sheds0 = br.counter("write_sheds")
            acks = self._write_block(mc, "/shed/internal", None,
                                     b"relay" * 1000)
            assert acks == [(0, dt.ACK_SUCCESS), (1, dt.ACK_SUCCESS)]
            assert br.counter("write_sheds") == sheds0
            # the same op under a spent tenant's name is refused
            acks = self._write_block(mc, "/shed/named", "raw", b"x" * 5000)
            assert [st for _, st in acks] == [dt.ACK_SHED] * 2


# --------------------------------------------------- noisy neighbor e2e


class TestNoisyNeighbor:
    def test_flood_sheds_hog_while_light_tenant_reads(self):
        """The acceptance matrix: tenant A floods writes past its rate;
        tenant B keeps reading.  A gets a structured retryable ShedError
        (refused AT ADMISSION — no mid-pipeline timeout), B's ops all
        complete, the per-tenant shed counters show the asymmetry, and
        no circuit breaker opens from shedding alone."""
        from hdrf_tpu.client.filesystem import HdrfClient
        from hdrf_tpu.config import ClientConfig
        from hdrf_tpu.testing.minicluster import MiniCluster
        from hdrf_tpu.utils import prom

        retry.reset_breakers()
        hog_sheds0 = _QOS.counter("tenant_sheds|tenant=hog,op=write")
        light_sheds0 = _QOS.counter("tenant_sheds|tenant=light,op=read")
        # one DN so every block shares one admission gate (with more DNs
        # each write head charges its own bucket and the flood would need
        # to overdraw every head before shedding)
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=1 << 20,
                         reduction_overrides={
                             "qos_tenant_rate_mb_s": 0.05,
                             "qos_tenant_burst_mb": 0.25,
                         }) as mc:
            rng = np.random.default_rng(14)
            small = rng.integers(0, 256, size=64 * 1024,
                                 dtype=np.uint8).tobytes()
            with mc.client("setup") as c:
                c.write("/qos/b", small, scheme="dedup_lz4")

            # ---- tenant A floods: first write rides the burst, the
            # second is refused at admission with a retry-after hint the
            # 3 s budget cannot cover (hint ~10 s at 0.05 MB/s)
            flood = rng.integers(0, 256, size=768 * 1024,
                                 dtype=np.uint8).tobytes()
            hog = HdrfClient(mc.nn_addrs(0)[0], name="hog",
                             config=ClientConfig(op_deadline_s=3.0))
            try:
                hog.write("/qos/flood1", flood, scheme="dedup_lz4")
                t0 = time.monotonic()
                with pytest.raises(qos.ShedError) as ei:
                    hog.write("/qos/flood2", flood, scheme="dedup_lz4")
                shed_latency = time.monotonic() - t0
                assert ei.value.retry_after_s > 0
                # refused at the door, not timed out mid-pipeline: the
                # 3 s deadline was NOT burned waiting
                assert shed_latency < 2.5, \
                    f"shed took {shed_latency:.2f}s — that's a timeout"
            finally:
                hog.close()

            # ---- tenant B's reads complete under the flood
            with mc.client("light") as c:
                for _ in range(3):
                    assert c.read("/qos/b") == small

            # ---- per-tenant asymmetry on the qos registry (and /prom
            # via the same snapshots render)
            assert _QOS.counter("tenant_sheds|tenant=hog,op=write") \
                > hog_sheds0
            assert _QOS.counter("tenant_sheds|tenant=light,op=read") \
                == light_sheds0
            text = prom.render(metrics.all_snapshots())
            assert 'hdrf_tenant_sheds_total{' in text
            assert 'tenant="hog"' in text

            # ---- sheds surface on /health without degrading the verdict
            # (the NN aggregates DN heartbeat stats — allow one beat)
            with mc.client("probe") as c:
                _wait(lambda: c._call("cluster_status")
                      ["qos_sheds_total"] >= 1,
                      msg="qos_sheds_total heartbeat aggregation")

            # ---- shedding alone never opens a breaker
            open_edges = [n for n, b in retry.all_breakers().items()
                          if b.state == "open"]
            assert not open_edges, f"breakers opened: {open_edges}"

    def test_shed_ack_round_trip_honors_hint_then_admits(self):
        """Wire contract: the DN refuses a streamed block with ACK_SHED
        acks carrying the retry-after hint (ms in the seqno field); a
        client WITHOUT a deadline honors the hint — sleeps it out — and
        the retried block is then admitted, so the write succeeds on the
        second attempt instead of erroring or hot-looping."""
        from hdrf_tpu.testing.minicluster import MiniCluster

        seen0 = metrics.registry("client").counter("write_sheds_seen")
        recv0 = metrics.registry("block_receiver").counter("write_sheds")
        with MiniCluster(n_datanodes=1, replication=1,
                         block_size=1 << 20,
                         reduction_overrides={
                             "qos_tenant_rate_mb_s": 0.1,
                             "qos_tenant_burst_mb": 0.1,
                         }) as mc:
            rng = np.random.default_rng(7)
            data = rng.integers(0, 256, size=256 * 1024,
                                dtype=np.uint8).tobytes()
            data2 = rng.integers(0, 256, size=64 * 1024,
                                 dtype=np.uint8).tobytes()
            with mc.client("wirehog") as c:
                c.write("/wire/a", data, scheme="dedup_lz4")
                # bucket ~150 KiB in deficit: attempt 1 sheds with a
                # ~1.5 s hint, the client waits it out, attempt 2 admits
                t0 = time.monotonic()
                c.write("/wire/b", data2, scheme="dedup_lz4")
                elapsed = time.monotonic() - t0
            # verify under a fresh tenant: wirehog's own bucket is still
            # paying off the overdraft and would shed the read as well
            with mc.client("wireverify") as c:
                assert c.read("/wire/b") == data2
        assert metrics.registry("block_receiver").counter(
            "write_sheds") > recv0, "the DN never shed on the wire"
        assert metrics.registry("client").counter(
            "write_sheds_seen") > seen0, "the client never saw ACK_SHED"
        # the hint was honored: no hot-loop (>=1 s of the ~1.5 s hint),
        # no pathological wait either
        assert 0.9 < elapsed < 20.0


# ------------------------------------------------- k+δ hedged EC reads


class TestEcStripeHedge:
    def test_stalled_stripe_holder_does_not_stall_degraded_read(self):
        """The straggler acceptance: demote a block to RS(2,1) stripes,
        stall ONE stripe holder via the "ec.stripe_hedge" fault point,
        and the degraded read must complete from the other k legs (the
        hedge fires at the p95 floor) without waiting out the stall."""
        from hdrf_tpu.testing.minicluster import MiniCluster

        retry.reset_breakers()
        with MiniCluster(n_datanodes=4, block_size=256 * 1024,
                         container_size=32 * 1024) as mc:
            mc.namenode.config.ec_data_shards = 2
            mc.namenode.config.ec_parity_shards = 1
            rng = np.random.default_rng(41)
            data = rng.integers(0, 256, size=150_000,
                                dtype=np.uint8).tobytes()
            with mc.client("hedge") as c:
                c.write("/hedge/a", data, scheme="dedup_lz4")
                assert c.read("/hedge/a") == data
                mc.namenode.config.ec_demote_after_s = 0.3
                time.sleep(0.3)
                _wait(lambda: c._call("ec_status")["demoted_blocks"] >= 1,
                      msg="block demotion")

                owner = next(dn for dn in mc.datanodes
                             if dn is not None and dn.index.stats()
                             ["striped_containers"] > 0)
                # cold-restart the owner: the container cache must miss so
                # the read goes sealed-file -> stripe gather
                oid = int(owner.dn_id.split("-")[1])
                mc.stop_datanode(oid)
                mc.restart_datanode(oid)
                mc.wait_for_datanodes(4)
                owner = mc.datanodes[oid]
                man = next(iter(owner.index.stripe_manifests().values()))
                k = int(man["k"])
                victim = next(man["holders"][i][0] for i in range(k)
                              if man["holders"][i][0] != owner.dn_id)

                stalled = []

                def _stall(holder=None, **kw):
                    if holder == victim:
                        stalled.append(holder)
                        time.sleep(6.0)

                fired0 = _EC.counter("ec_hedges_fired")
                wins0 = _EC.counter("ec_hedge_wins")
                with fault_injection.inject("ec.stripe_hedge", _stall):
                    t0 = time.monotonic()
                    assert c.read("/hedge/a") == data
                    elapsed = time.monotonic() - t0
                assert stalled, "fault point never saw the victim leg"
                assert elapsed < 5.0, \
                    f"read waited out the straggler ({elapsed:.1f}s)"
                assert _EC.counter("ec_hedges_fired") > fired0
                assert _EC.counter("ec_hedge_wins") > wins0

    def test_delta_zero_restores_serial_gather(self):
        """ec_read_hedge_delta=0 must take the pre-hedging serial path
        (no hedge counters move) and still reconstruct bit-identically."""
        from hdrf_tpu.testing.minicluster import MiniCluster

        with MiniCluster(n_datanodes=4, block_size=256 * 1024,
                         container_size=32 * 1024,
                         reduction_overrides={
                             "ec_read_hedge_delta": 0,
                         }) as mc:
            mc.namenode.config.ec_data_shards = 2
            mc.namenode.config.ec_parity_shards = 1
            rng = np.random.default_rng(43)
            data = rng.integers(0, 256, size=120_000,
                                dtype=np.uint8).tobytes()
            with mc.client("serial") as c:
                c.write("/serial/a", data, scheme="dedup_lz4")
                mc.namenode.config.ec_demote_after_s = 0.3
                time.sleep(0.3)
                _wait(lambda: c._call("ec_status")["demoted_blocks"] >= 1,
                      msg="block demotion")
                oid = next(i for i, dn in enumerate(mc.datanodes)
                           if dn is not None and dn.index.stats()
                           ["striped_containers"] > 0)
                mc.stop_datanode(oid)
                mc.restart_datanode(oid)
                mc.wait_for_datanodes(4)
                fired0 = _EC.counter("ec_hedges_fired")
                assert c.read("/serial/a") == data
                assert _EC.counter("ec_hedges_fired") == fired0
