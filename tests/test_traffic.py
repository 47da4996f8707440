"""Serving-edge traffic tests: bounded admission, typed BUSY
backpressure, shed policies, and the open-loop harness.

The load-bearing invariant throughout is conservation — every offered
request is exactly one of {replied, rejected, shed, still queued/
inflight}; nothing is ever silently dropped (ISSUE 8 acceptance)."""

import queue as _queue
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu.core.errors import ServerBusyError, StreamError
from nnstreamer_tpu.edge import QueryServer
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.traffic import (
    AdmissionQueue, EchoServer, bursty_arrivals, poisson_arrivals,
    run_against_echo)


@pytest.fixture(autouse=True)
def _clean_servers():
    yield
    QueryServer.reset_all()


def _conserved(c: dict) -> bool:
    """Both accounting invariants from the admission contract."""
    return (c["offered"] == c["admitted"] + sum(c["rejected"].values())
            and c["admitted"] == c["replied"] + sum(c["shed"].values())
            + c["depth"] + c["inflight"])


# -- AdmissionQueue unit tests (no sockets) ----------------------------------

class TestAdmissionQueue:
    def test_reject_newest_bounds_queue(self):
        q = AdmissionQueue(max_pending=3)
        for i in range(3):
            assert q.offer(i).admitted
        d = q.offer(99)
        assert not d.admitted and d.cause == "queue_full"
        assert d.queue_depth == 3 and d.retry_after_ms > 0
        c = q.counters()
        assert c["offered"] == 4 and c["admitted"] == 3
        assert c["rejected"] == {"queue_full": 1}
        assert c["depth_peak"] == 3 and _conserved(c)

    def test_reject_oldest_sheds_victim_still_admits(self):
        q = AdmissionQueue(max_pending=2, shed_policy="reject-oldest")
        q.offer("a"), q.offer("b")
        d = q.offer("c")
        assert d.admitted
        assert d.victims == ["a"] and d.victim_cause == "reject_oldest"
        c = q.counters()
        assert c["shed"] == {"reject_oldest": 1} and c["depth"] == 2
        # FIFO order after the shed: b then c
        assert q.get(timeout=1) == "b" and q.get(timeout=1) == "c"
        q.note_replied(), q.note_replied()
        assert _conserved(q.counters())

    def test_deadline_drop_purges_expired(self):
        q = AdmissionQueue(max_pending=8, shed_policy="deadline-drop")
        rushed = SimpleNamespace(meta={"deadline_ms": 5})
        d = q.offer(rushed, now=100.0)
        assert d.admitted
        # 200ms later its 5ms budget is long gone: the next offer purges
        d = q.offer(SimpleNamespace(meta={}), now=100.2)
        assert d.admitted
        assert d.victims == [rushed] and d.victim_cause == "deadline"
        c = q.counters()
        assert c["shed"] == {"deadline": 1} and c["depth"] == 1
        assert _conserved(c)

    def test_deadline_drop_full_without_expiries_rejects_newest(self):
        q = AdmissionQueue(max_pending=1, shed_policy="deadline-drop")
        assert q.offer(SimpleNamespace(meta={}), now=1.0).admitted
        d = q.offer(SimpleNamespace(meta={}), now=1.001)
        assert not d.admitted and d.cause == "queue_full"

    def test_inflight_bound_counts_dequeued_work(self):
        q = AdmissionQueue(max_pending=10, max_inflight=2)
        assert q.offer("a").admitted and q.offer("b").admitted
        assert q.offer("c").cause == "inflight_full"
        q.get(timeout=1)                     # a queued->inflight
        assert q.offer("c").cause == "inflight_full"   # still 2 total
        q.note_replied()                     # a done
        assert q.offer("c").admitted
        assert _conserved(q.counters())

    def test_note_failed_counts_as_shed(self):
        q = AdmissionQueue(max_pending=4)
        q.offer("a")
        q.get(timeout=1)
        q.note_failed("dispatch_error")
        c = q.counters()
        assert c["shed"] == {"dispatch_error": 1}
        assert c["inflight"] == 0 and _conserved(c)

    def test_sentinel_bypasses_admission(self):
        q = AdmissionQueue(max_pending=1)
        assert q.offer("real").admitted
        q.put_nowait(None)                   # full queue must not refuse
        assert q.get(timeout=1) == "real"
        assert q.get(timeout=1) is None
        c = q.counters()
        assert c["offered"] == 1             # sentinel never counted
        assert c["inflight"] == 1            # only the real item

    def test_get_timeout_raises_queue_empty(self):
        with pytest.raises(_queue.Empty):
            AdmissionQueue().get(timeout=0.05)

    def test_shed_remaining_closes_then_reopen(self):
        q = AdmissionQueue(max_pending=8)
        q.offer("a"), q.offer("b")
        assert q.shed_remaining() == ["a", "b"]
        d = q.offer("c")
        assert not d.admitted and d.cause == "shutdown"
        c = q.counters()
        assert c["shed"] == {"shutdown": 2}
        assert c["rejected"] == {"shutdown": 1} and _conserved(c)
        q.reopen()
        assert q.offer("c").admitted

    def test_configure_validates(self):
        q = AdmissionQueue()
        with pytest.raises(ValueError, match="max_pending"):
            q.configure(max_pending=0)
        with pytest.raises(ValueError, match="max_inflight"):
            q.configure(max_inflight=-1)
        with pytest.raises(ValueError, match="shed_policy"):
            q.configure(shed_policy="drop-table")

    def test_retry_after_tracks_service_rate(self):
        q = AdmissionQueue(max_pending=4)
        assert q.offer(0).retry_after_ms == 50.0   # no estimate yet
        for _ in range(3):
            q.get(timeout=1)
            q.note_replied()
            q.offer(0)
        # EWMA exists now: suggestion scales with queue depth, clamped
        d = q.offer(1)
        assert 1.0 <= d.retry_after_ms <= 10_000.0

    def test_retry_after_cold_start_is_finite_positive(self):
        """ISSUE 12 satellite: a freshly started (or freshly joined)
        server has NO reply EWMA yet — every rejection it issues must
        still carry a finite positive retry hint, or a retry:N:backoff
        client divides by it garbage. Degenerate EWMA states (a stuck
        clock, an overflowed estimate) must degrade to the clamps, not
        to inf/NaN on the wire."""
        q = AdmissionQueue(max_pending=1)
        q.offer("a")
        d = q.offer("b")                       # cold: no EWMA at all
        assert not d.admitted
        assert d.retry_after_ms == 50.0        # _DEFAULT_RETRY_MS
        for bad in (0.0, -1.0, float("inf"), float("nan")):
            q._ewma_reply_s = bad
            d = q.offer("b")
            assert d.retry_after_ms == 50.0, \
                f"ewma={bad} leaked a useless hint {d.retry_after_ms}"
        q._ewma_reply_s = 1e306                # est overflows to inf
        d = q.offer("b")
        import math
        assert math.isfinite(d.retry_after_ms)
        assert d.retry_after_ms == 10_000.0    # upper clamp


# -- live max_pending shrink (ISSUE 15 satellite) ----------------------------

class TestConfigureShrink:
    """Shrinking max_pending below the live depth must never strand or
    double-count an entry: under reject-oldest the excess oldest
    entries are shed (cause bound_shrink) and returned as victims; the
    conservation invariants hold exactly at every step."""

    def test_shrink_sheds_oldest_exactly_once(self):
        q = AdmissionQueue(max_pending=8, shed_policy="reject-oldest")
        for i in range(8):
            assert q.offer(i).admitted
        victims = q.configure(max_pending=3)
        assert victims == [0, 1, 2, 3, 4]      # oldest first
        c = q.counters()
        assert c["shed"] == {"bound_shrink": 5}
        assert c["depth"] == 3 and _conserved(c)
        # survivors drain in FIFO order, nothing stranded
        assert [q.get(timeout=0.1) for _ in range(3)] == [5, 6, 7]

    def test_shrink_under_other_policies_drains_naturally(self):
        for policy in ("reject-newest", "deadline-drop"):
            q = AdmissionQueue(max_pending=8, shed_policy=policy)
            for i in range(8):
                assert q.offer(i).admitted
            assert q.configure(max_pending=3) == []
            c = q.counters()
            assert c["depth"] == 8 and c["shed"] == {} and _conserved(c)
            # the bound still applies to new arrivals immediately
            assert not q.offer(99).admitted

    def test_shrink_never_evicts_sentinel(self):
        q = AdmissionQueue(max_pending=8, shed_policy="reject-oldest")
        for i in range(4):
            q.offer(i)
        q.put_nowait(None)                     # teardown sentinel
        victims = q.configure(max_pending=2)
        assert None not in victims
        assert victims == [0, 1, 2]
        drained = [q.get(timeout=0.1) for _ in range(2)]
        assert drained == [3, None]            # sentinel survived

    def test_shrink_grow_shrink_keeps_books_exact(self):
        q = AdmissionQueue(max_pending=16, shed_policy="reject-oldest")
        for i in range(16):
            q.offer(i)
        q.configure(max_pending=5)
        assert _conserved(q.counters())
        q.configure(max_pending=32)            # growth sheds nothing
        c = q.counters()
        assert c["depth"] == 5 and _conserved(c)
        for i in range(100, 110):
            q.offer(i)
        q.configure(max_pending=2)
        c = q.counters()
        assert c["depth"] == 2 and _conserved(c)

    def test_tenant_mode_shrink_trims_per_class_bounds(self):
        from nnstreamer_tpu.serving.tenancy import (
            TENANT_META, TenantTable)
        from nnstreamer_tpu.traffic.loadgen import (
            _tenant_conservation_ok)

        q = AdmissionQueue(max_pending=8, shed_policy="reject-oldest")
        q.set_tenants(TenantTable.from_dict(
            {"default": "a", "tenants": [
                {"name": "a", "weight": 1.0},
                {"name": "b", "weight": 1.0}]}))
        for i in range(4):
            for t in ("a", "b"):
                d = q.offer(SimpleNamespace(meta={TENANT_META: t},
                                            pts=i))
                assert d.admitted
        victims = q.configure(max_pending=4)   # bounds 4+4 -> 2+2
        assert len(victims) == 4
        c = q.counters()
        assert c["shed"] == {"bound_shrink": 4}
        for cls in ("a", "b"):
            assert c["classes"][cls]["shed"] == {"bound_shrink": 2}
            assert c["classes"][cls]["depth"] == 2
        assert _tenant_conservation_ok(c)

    def test_conservation_exact_under_flood_with_live_shrinks(self):
        """The regression the satellite asks for: a producer floods,
        a consumer serves, and the bound is yanked up and down live —
        the books must close exactly at every sampled instant."""
        q = AdmissionQueue(max_pending=32, shed_policy="reject-oldest")
        stop = threading.Event()

        def flood():
            i = 0
            while not stop.is_set():
                q.offer(i)
                i += 1

        def serve():
            while not stop.is_set():
                try:
                    item = q.get(timeout=0.01)
                except _queue.Empty:
                    continue
                if item is not None:
                    q.note_replied()

        threads = [threading.Thread(target=flood, daemon=True),
                   threading.Thread(target=serve, daemon=True)]
        for t in threads:
            t.start()
        try:
            for mp in (4, 32, 3, 16, 2, 32) * 5:
                q.configure(max_pending=mp)
                assert _conserved(q.counters()), \
                    f"books broke right after shrink to {mp}"
                time.sleep(0.002)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=2)
        assert _conserved(q.counters())
        assert q.counters()["shed"].get("bound_shrink", 0) > 0


# -- arrival processes -------------------------------------------------------

class TestArrivals:
    def test_poisson_deterministic_and_on_rate(self):
        a = poisson_arrivals(100.0, 400, np.random.default_rng(7))
        b = poisson_arrivals(100.0, 400, np.random.default_rng(7))
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) >= 0) and a[0] >= 0
        # 400 samples at 100 rps: mean inter-arrival 10ms +/- 30%
        assert 0.007 < np.mean(np.diff(a)) < 0.013

    def test_bursty_alternates_phases(self):
        a = bursty_arrivals(500, rate_high_hz=500.0, rate_low_hz=10.0,
                            mean_dwell_s=0.05,
                            rng=np.random.default_rng(3))
        b = bursty_arrivals(500, rate_high_hz=500.0, rate_low_hz=10.0,
                            mean_dwell_s=0.05,
                            rng=np.random.default_rng(3))
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) >= 0)
        gaps = np.diff(a)
        # both phases visible: some burst-rate gaps, some trough gaps
        assert np.min(gaps) < 1 / 100.0 < np.max(gaps)


# -- flood the real server (the ISSUE acceptance scenario) -------------------

class TestFlood:
    def test_overload_sheds_typed_and_loses_nothing(self):
        r = run_against_echo(pattern="poisson", load_x=2.0, n=60,
                             service_ms=5.0, max_pending=4, seed=7)
        assert r["rejected"] > 0, "2x overload must shed"
        assert r["lost"] == 0, "every request replied or typed-rejected"
        assert not r["server_crashed"]
        assert r["busy_causes"].get("queue_full", 0) > 0
        adm = r["admission"]
        assert adm["max_pending"] == 4          # knob reached the queue
        assert adm["offered"] == r["offered"]
        assert _conserved(adm)
        assert r["queue_depth_peak"] <= 4

    def test_deadline_drop_purges_live(self):
        # a 20ms budget against 5ms service + overload: queued frames
        # expire and are shed with the deadline cause, never lost
        r = run_against_echo(pattern="poisson", load_x=2.5, n=60,
                             service_ms=5.0, max_pending=8,
                             shed_policy="deadline-drop",
                             p99_budget_ms=20.0, seed=5)
        assert r["busy_causes"].get("deadline", 0) > 0
        assert r["lost"] == 0 and _conserved(r["admission"])

    def test_below_knee_sheds_nothing(self):
        r = run_against_echo(pattern="poisson", load_x=0.4, n=40,
                             service_ms=5.0, max_pending=8, seed=7)
        assert r["rejected"] == 0 and r["lost"] == 0
        assert r["completed"] == 40


# -- client backpressure through the error-policy machinery ------------------

def _client_pipe(port, policy, n, max_in_flight=2, timeout=30):
    extra = f"error_policy={policy} " if policy else ""
    pipe = nns.parse_launch(
        f"appsrc name=src dims=8:1 types=float32 ! "
        f"tensor_query_client name=qc port={port} timeout={timeout} "
        f"max_in_flight={max_in_flight} {extra}! tensor_sink name=sink")
    rn = nns.PipelineRunner(pipe).start()
    for i in range(n):
        pipe.get("src").push(
            TensorBuffer.of(np.full((8, 1), float(i), np.float32), pts=i))
    pipe.get("src").end()
    return pipe, rn


class TestClientBackpressure:
    def test_retry_policy_recovers_every_frame(self):
        # max_inflight=1: any frame offered while another is queued or
        # in service is refused, so a 2-deep client window guarantees
        # rejections — retry must still deliver all frames, in order
        srv = EchoServer(service_ms=40.0, max_pending=16, max_inflight=1)
        try:
            pipe, rn = _client_pipe(srv.port, "retry:10:30", n=6)
            rn.wait(60)
            st = rn.stats()
            rn.stop()
            res = pipe.get("sink").results
            assert [r.pts for r in res] == list(range(6))
            # the test is vacuous unless BUSY actually happened
            assert st["qc"]["query_busy"] >= 1
            assert st["qc"]["retries"] >= 1
            assert not srv.crashed()
        finally:
            srv.stop()

    def test_skip_policy_sheds_client_side(self):
        srv = EchoServer(service_ms=30.0, max_pending=16, max_inflight=1)
        try:
            pipe, rn = _client_pipe(srv.port, "skip", n=8)
            rn.wait(60)                      # no error: skip absorbs
            st = rn.stats()
            rn.stop()
            res = pipe.get("sink").results
            assert 1 <= len(res) < 8         # some delivered, some shed
            assert st["qc"]["query_busy"] >= 1
            pts = [r.pts for r in res]
            assert pts == sorted(pts)        # gaps allowed, reorder not
        finally:
            srv.stop()

    def test_fail_fast_surfaces_typed_busy(self):
        srv = EchoServer(service_ms=50.0, max_pending=16, max_inflight=1)
        try:
            pipe, rn = _client_pipe(srv.port, None, n=4)
            with pytest.raises(StreamError, match="rejected frame"):
                rn.wait(30)
            assert isinstance(rn._error, ServerBusyError)
            assert rn._error.cause == "inflight_full"
            rn.stop()
        finally:
            srv.stop()


# -- BatchedQueryServer shutdown race + stats snapshot -----------------------

class TestBatchedShutdown:
    def _server(self, **kw):
        import jax.numpy as jnp

        from nnstreamer_tpu.backends.xla import ModelBundle
        from nnstreamer_tpu.edge import BatchedQueryServer
        from nnstreamer_tpu.tensor.dtypes import DType
        from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec

        QueryServer.reset_all()
        w = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
        bundle = ModelBundle(
            fn=lambda p, x: (x @ p["w"],),
            params={"w": w},
            in_spec=TensorsSpec.of(TensorInfo((1, 4), DType.FLOAT32)),
            out_spec=TensorsSpec.of(TensorInfo((1, 3), DType.FLOAT32)),
            name="linear")
        return BatchedQueryServer(bundle, sid=33, port=0, **kw)

    def test_close_mid_stream_answers_or_sheds_every_frame(self):
        """The PR-7 race: close() while frames are queued must neither
        hang a client nor silently drop — each in-flight frame ends as
        RESULT or typed BUSY, and close() returns promptly."""
        import nnstreamer_tpu.edge.protocol as P
        from nnstreamer_tpu.edge.wire import encode_buffer

        srv = self._server(bucket=4, max_delay_ms=50.0)
        done = threading.Event()
        got = {"result": 0, "busy": 0}
        n_sent = 12

        def on_msg(mtype, payload):
            if mtype == P.T_RESULT:
                got["result"] += 1
            elif mtype == P.T_BUSY:
                got["busy"] += 1
            if got["result"] + got["busy"] >= n_sent:
                done.set()

        cli = P.MsgClient("127.0.0.1", srv.port, on_message=on_msg)
        try:
            cli.send(P.T_HELLO, b'{"dims": "1:4", "types": "float32"}')
            time.sleep(0.3)                  # let the ACK land
            x = np.ones((1, 4), np.float32)
            for i in range(n_sent):
                cli.send(P.T_DATA, encode_buffer(
                    TensorBuffer.of(x, pts=i)))
            t0 = time.monotonic()
            srv.close()                      # race: frames still queued
            assert time.monotonic() - t0 < 15
            assert done.wait(10), (
                f"lost frames: {got} of {n_sent} answered")
            assert got["result"] + got["busy"] == n_sent
            st = srv.stats()
            assert st["admitted"] == st["replied"] + st["shed"]
        finally:
            cli.close()

    def test_stats_snapshot_is_thread_safe_under_load(self):
        import nnstreamer_tpu as nns

        srv = self._server(bucket=4, max_delay_ms=5.0)
        errs = []

        def poll():
            for _ in range(200):
                st = srv.stats()
                if not {"frames", "batches", "admitted",
                        "replied"} <= set(st):
                    errs.append(f"missing keys: {sorted(st)}")
                    return
        try:
            poller = threading.Thread(target=poll)
            poller.start()
            pipe = nns.parse_launch(
                f"appsrc name=src dims=4:1 types=float32 ! "
                f"tensor_query_client port={srv.port} timeout=30 "
                f"max_in_flight=4 ! tensor_sink name=sink")
            rn = nns.PipelineRunner(pipe).start()
            for i in range(16):
                pipe.get("src").push(TensorBuffer.of(
                    np.ones((1, 4), np.float32), pts=i))
            pipe.get("src").end()
            rn.wait(30)
            rn.stop()
            poller.join(10)
            assert not errs, errs[0]
            assert len(pipe.get("sink").results) == 16
        finally:
            srv.close()

    def test_close_is_idempotent(self):
        """Regression: a supervisor-driven close racing (or repeating)
        a user close must be a no-op — no double-shed, no error on an
        already-stopped dispatcher, stable stats."""
        srv = self._server(bucket=4, max_delay_ms=5.0)
        srv.close()
        st = srv.stats()
        srv.close()                          # second close: no-op
        assert srv.stats() == st
        srv.close()                          # and a third, for luck


# -- observability ------------------------------------------------------------

class TestShedObservability:
    def test_tracer_counts_sheds_across_ring_wrap(self):
        from nnstreamer_tpu.runtime.tracing import Tracer

        tr = Tracer(max_events=4)            # tiny ring: force wrap
        for i in range(10):
            tr.record_shed("query_server_5", "queue_full",
                           float(i), pts=i)
        tr.record_shed("query_server_5", "shutdown", 11.0)
        counts = tr.shed_counts()
        assert counts["query_server_5"] == {"queue_full": 10,
                                            "shutdown": 1}
        assert tr.summary()["sheds"] == counts

    def test_serversrc_extra_stats_surface_admission(self):
        r = run_against_echo(pattern="poisson", load_x=2.0, n=40,
                             service_ms=5.0, max_pending=4, seed=3)
        adm = r["admission"]
        assert adm["rejected"].get("queue_full", 0) == r["rejected"]


# -- distributed tracing at the serving edge ---------------------------------

from nnstreamer_tpu.runtime.tracing import (  # noqa: E402
    ensure_trace_ctx, get_trace_ctx, hop_spans)


class TestEdgeTracing:
    def test_busy_retry_reuses_trace_id(self):
        """ISSUE 11 regression: a client BUSY-retry re-sends the SAME
        buffer, so the trace context (and its id) must survive — a new
        client_send hop is appended, never a fresh id. A fresh id per
        attempt would shatter one request into unjoinable timelines.

        No clock decides the BUSY: the server (`max_inflight=1`) holds
        another client's frame at a gate, so the traced client's first
        offer is refused for as long as the gate is shut, and the test
        opens it once it has seen the refusal. The traced client keeps
        one frame in flight, where a retry is exact: the refused frame
        is the one sent again."""
        from nnstreamer_tpu.backends.custom import (
            register_custom_easy, unregister_custom_easy)

        gate, held = threading.Event(), threading.Event()

        def serve(ts):
            held.set()
            assert gate.wait(60), "the test never opened the gate"
            return ts

        register_custom_easy("traffic_gated_echo", serve)
        srv = nns.parse_launch(
            "tensor_query_serversrc name=src id=9911 port=0 dims=8:1 "
            "types=float32 max_pending=16 max_inflight=1 ! "
            "tensor_filter framework=custom model=traffic_gated_echo ! "
            "tensor_query_serversink id=9911")
        gate.set()              # negotiation probes the model once
        srv_rn = nns.PipelineRunner(srv).start()
        runners = [srv_rn]
        try:
            gate.clear(), held.clear()
            port = srv.get("src").port
            # the frame that fills the server: admitted, held at the gate
            _, blocker = _client_pipe(port, None, n=1, max_in_flight=1,
                                      timeout=60)
            runners.append(blocker)
            assert held.wait(30), "the blocking frame never reached service"
            pipe = nns.parse_launch(
                f"appsrc name=src dims=8:1 types=float32 ! "
                f"tensor_query_client name=qc port={port} timeout=60 "
                f"max_in_flight=1 error_policy=retry:12:20 "
                f"! tensor_sink name=sink")
            rn = nns.PipelineRunner(pipe).start()
            runners.append(rn)
            sent_ids = {}
            for i in range(3):
                buf = TensorBuffer.of(
                    np.full((8, 1), float(i), np.float32), pts=i)
                sent_ids[i] = ensure_trace_ctx(buf.meta)["id"]
                pipe.get("src").push(buf)
            pipe.get("src").end()
            # retry:12:20 keeps offering for 80 s; the gate opens as
            # soon as one offer has been refused
            deadline = time.monotonic() + 30
            while rn.stats()["qc"]["query_busy"] < 1:
                assert time.monotonic() < deadline, "no offer was refused"
                time.sleep(0.01)
            gate.set()
            rn.wait(60)
            blocker.wait(60)
            st = rn.stats()
            res = pipe.get("sink").results
            assert [r.pts for r in res] == list(range(3))
            assert st["qc"]["query_busy"] >= 1 and st["qc"]["retries"] >= 1
            for r in res:
                ctx = get_trace_ctx(r.meta)
                assert ctx is not None, f"pts={r.pts} lost its context"
                # the invariant under test: id survives the retry
                assert ctx["id"] == sent_ids[int(r.pts)]
                hop_names = [h["hop"] for h in ctx["hops"]]
                assert hop_names.count("client_send") >= 1
                assert "reply" in hop_names
            # the refused frame's timeline shows the retry as extra
            # client_send hops on ONE id
            first = get_trace_ctx(res[0].meta)
            assert hop_spans(first["hops"]).get("retries")
            assert [h["hop"] for h in first["hops"]].count(
                "client_send") >= 2
            assert srv_rn._error is None
        finally:
            gate.set()
            for r in reversed(runners):
                r.stop()
            unregister_custom_easy("traffic_gated_echo")

    def test_open_loop_trace_reports_hop_breakdown(self):
        r = run_against_echo(pattern="poisson", load_x=0.5, n=30,
                             service_ms=4.0, max_pending=16, seed=2,
                             trace=True)
        assert r["lost"] == 0
        assert r["traced_replies"] == r["completed"]
        hb = r["hop_breakdown"]
        assert len(hb["trace_id"]) == 16
        assert hb["hops"][0] == "client_send"
        assert hb["hops"][-1] == "client_recv"
        spans = hb["spans"]
        # echo server: admission + service + reply stages must resolve
        assert "admission_wait_ms" in spans
        assert spans["total_ms"] == pytest.approx(
            hb["latency_ms"], rel=0.05, abs=1.0)

    def test_untraced_run_carries_no_ctx(self):
        # tracing stays strictly opt-in: without trace=True nothing in
        # the serving path invents a context (the stamp sites are
        # no-ops), so the known-capacity numbers stay comparable
        r = run_against_echo(pattern="poisson", load_x=0.5, n=20,
                             service_ms=4.0, max_pending=16, seed=2)
        assert "hop_breakdown" not in r
        assert "traced_replies" not in r
