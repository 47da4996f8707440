"""Tracing subsystem: span events, interlatency percentiles, queue
gauges, Chrome-trace export, drop/error accounting (CPU-only; timing
assertions use budgets generous enough for CI jitter)."""

import json
import time

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu import parse_launch, register_custom_easy, run_pipeline
from nnstreamer_tpu.backends.custom import unregister_custom_easy
from nnstreamer_tpu.core.errors import StreamError
from nnstreamer_tpu.runtime.scheduler import PipelineRunner
from nnstreamer_tpu.runtime.tracing import (
    NULL_TRACER, SOURCE_TS_META, NullTracer, Tracer, percentile)


@pytest.fixture(autouse=True)
def _clean_models():
    names = []

    def reg(name, *a, **kw):
        names.append(name)
        return register_custom_easy(name, *a, **kw)

    yield reg
    for n in names:
        unregister_custom_easy(n)


def _run_traced(desc, timeout=30, **kw):
    p = parse_launch(desc)
    runner = PipelineRunner(p, trace=True, **kw).start()
    try:
        runner.wait(timeout)
    finally:
        runner.stop()
    return p, runner


SLEEP_S = 0.01


def _sleepy(ts):
    time.sleep(SLEEP_S)
    return ts


class TestTracerCore:
    def test_default_is_noop(self):
        p = parse_launch("videotestsrc width=4 height=4 num-buffers=2 "
                         "! tensor_converter ! tensor_sink")
        runner = PipelineRunner(p)
        assert runner.tracer is NULL_TRACER
        assert runner.tracer.active is False
        runner.start()
        runner.wait(10)
        runner.stop()
        # a NullTracer records nothing and has no ring to inspect
        assert isinstance(runner.tracer, NullTracer)

    def test_span_is_the_one_generic_call(self):
        tr = Tracer()
        tr.span("llm", "e", "admit_full", 1.0, 1.5, step=3, rows=16)
        tr.span("backend", "e", "wait", 2.0, 2.25)
        tr.backend_span("e", "invoke", 3.0, 3.5, what="x", kernel="xla")
        tr.record_timer("e", 0.5, 4.0, input_depth=2)
        assert tr.events() == [
            ("X", "llm", "e", "admit_full", 1.0, 0.5,
             {"step": 3, "rows": 16}),
            ("X", "backend", "e", "wait", 2.0, 0.25, None),
            ("X", "backend", "e", "invoke", 3.0, 0.5,
             {"what": "x", "kernel": "xla"}),
            ("X", "element", "e", "timer", 0.5, 3.5, {"input_depth": 2})]
        # only a kernel= span is counted per kernel
        assert tr.kernel_spans() == {("e", "xla"): 1}
        ev = [e for e in tr.to_chrome_trace()["traceEvents"]
              if e.get("name") == "admit_full"][0]
        assert ev["cat"] == "llm" and ev["args"] == {"step": 3, "rows": 16}
        assert NULL_TRACER.span("llm", "e", "admit", 0.0, 1.0, k=1) is None
        assert NULL_TRACER.record_timer("e", 0.0, 1.0, input_depth=0) \
            is None

    def test_llm_requests_are_bounded_fifo(self):
        tr = Tracer()
        tr._max_requests = 8
        for i in range(11):
            tr.record_llm_request("e", f"r{i}", float(i), n_tokens=i)
        kept = [r[1] for r in tr.llm_requests()]
        # at the cap the oldest quarter goes, never the newest
        assert kept == [f"r{i}" for i in range(4, 11)]
        s = tr.summary()
        assert s["llm_requests"] == 11 and s["llm_requests_dropped"] == 4

    def test_forced_sync_is_stamped_on_the_ring_clock(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.runtime.sync import device_sync

        tr = Tracer()
        t0 = time.perf_counter()
        device_sync(jnp.ones((2,)), tracer=tr, name="e:decode")
        t1 = time.perf_counter()
        (ev,) = [e for e in tr.events() if e[3] == "forced_sync"]
        assert t0 <= ev[4] <= t1

    def test_percentile_nearest_rank(self):
        vals = sorted(float(i) for i in range(1, 101))
        assert percentile(vals, 50) == 50.0
        assert percentile(vals, 99) == 99.0
        assert percentile(vals, 100) == 100.0
        assert percentile([], 50) == 0.0

    def test_process_spans_per_element_ordered(self):
        p, runner = _run_traced(
            "videotestsrc width=4 height=4 num-buffers=6 ! "
            "tensor_converter name=conv ! tensor_sink name=out")
        spans = {}
        for ph, cat, name, label, ts, dur, args in runner.tracer.events():
            if ph == "X" and label == "process":
                spans.setdefault(name, []).append((ts, dur))
        # every non-source element got one process span per buffer...
        assert len(spans["conv"]) == 6
        assert len(spans["out"]) == 6
        # ...in monotonically increasing start order (one worker thread
        # per element: spans on one track never interleave)
        for name, ss in spans.items():
            starts = [t for t, _ in ss]
            assert starts == sorted(starts)
            assert all(d >= 0.0 for _, d in ss)

    def test_interlatency_percentiles_sleep_element(self, _clean_models):
        _clean_models("sleepy", _sleepy)
        p, runner = _run_traced(
            "videotestsrc width=4 height=4 num-buffers=8 ! tensor_converter "
            "! tensor_transform mode=typecast option=float32 "
            "! tensor_filter framework=custom model=sleepy "
            "! tensor_sink name=out")
        inter = runner.tracer.interlatency()
        assert "out" in inter
        r = inter["out"]
        assert r["n"] == 8
        # every frame crossed the sleeping filter: end-to-end latency at
        # the sink is at least the sleep, and percentiles are ordered
        assert r["p50_ms"] >= SLEEP_S * 1e3
        assert r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"] <= r["max_ms"]
        # the source-side converter saw the frame before the sleep: its
        # median must come in under the sink's
        conv = [v for k, v in inter.items() if k != "out"]
        assert conv and min(c["p50_ms"] for c in conv) < r["p50_ms"]

    def test_source_ts_stamped_in_meta(self):
        p, runner = _run_traced(
            "videotestsrc width=4 height=4 num-buffers=2 ! "
            "tensor_converter ! tensor_sink name=out")
        for buf in p.get("out").results:
            assert SOURCE_TS_META in buf.meta

    def test_queue_highwater_under_backpressure(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=20 ! "
            "tensor_converter ! tensor_sink name=out")
        sink = p.get("out")
        orig = sink.render

        def slow_render(buf):
            time.sleep(0.005)
            orig(buf)

        sink.render = slow_render
        runner = PipelineRunner(p, queue_capacity=2, trace=True).start()
        runner.wait(30)
        runner.stop()
        assert len(sink.results) == 20
        # the slow sink's queue filled to capacity — visible both in the
        # tracer gauge and the always-on stats high-water mark
        assert runner.tracer.queue_gauges()["out"]["peak"] >= 2
        assert runner.stats()["out"]["queue_peak"] >= 2

    def test_event_ring_is_bounded(self):
        tr = Tracer(max_events=16)
        for i in range(100):
            tr.instant("e", "tick", t=float(i))
        assert len(tr.events()) == 16
        assert tr.events_dropped == 84
        # the ring keeps the newest events
        assert tr.events()[-1][4] == 99.0


class TestChromeTrace:
    def test_schema_and_one_track_per_element(self, _clean_models):
        _clean_models("ident", lambda ts: ts)
        p, runner = _run_traced(
            "videotestsrc width=4 height=4 num-buffers=4 ! "
            "tensor_converter name=conv "
            "! tensor_transform mode=typecast option=float32 "
            "! tensor_filter framework=custom model=ident name=filt "
            "! tensor_sink name=out")
        doc = runner.tracer.to_chrome_trace("demo")
        # valid JSON round-trip of the Trace Event Format container
        doc = json.loads(json.dumps(doc))
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        tracks = {}
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("M", "X", "C", "i")
            assert "pid" in ev
            if ev["ph"] == "M" and ev["name"] == "thread_name":
                tracks[ev["args"]["name"]] = ev["tid"]
            if ev["ph"] == "X":
                assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
            if ev["ph"] == "C":
                assert "depth" in ev["args"]
        # one named track per element that produced events, unique tids
        for name in ("conv", "filt", "out"):
            assert name in tracks
        assert len(set(tracks.values())) == len(tracks)
        # spans reference declared tracks only
        declared = set(tracks.values())
        for ev in doc["traceEvents"]:
            if ev["ph"] == "X":
                assert ev["tid"] in declared

    def test_batch_flush_markers_and_batched_interlatency(self):
        from nnstreamer_tpu.tensor.buffer import TensorBuffer
        from nnstreamer_tpu.tensor.dtypes import DType
        from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec

        desc = ("appsrc name=in dims=4 types=float32 ! "
                "tensor_batch name=b max-batch=4 max-latency-ms=1000 ! "
                "tensor_unbatch ! tensor_sink name=out")
        p = parse_launch(desc)
        runner = PipelineRunner(p, trace=True).start()
        src = p.get("in")
        for i in range(10):
            src.push(TensorBuffer.of(np.full((4,), float(i), np.float32),
                                     pts=i))
        src.end()
        runner.wait(30)
        runner.stop()
        flushes = [(name, label, args) for ph, cat, name, label, ts, dur,
                   args in runner.tracer.events()
                   if ph == "i" and label.startswith("flush_")]
        # 10 frames at max-batch=4 → two full flushes + one EOS flush
        assert [l for _, l, _ in flushes].count("flush_full") == 2
        assert [l for _, l, _ in flushes].count("flush_eos") == 1
        assert {a["n"] for _, _, a in flushes} == {4, 2}
        # interlatency survives batch→unbatch: per-frame source stamps
        # ride in the dyn_batch frame metas and are restored downstream
        inter = runner.tracer.interlatency()
        assert inter["out"]["n"] == 10
        # the batcher's own interlatency comes from the oldest frame in
        # each batch (the deadline-bound one)
        assert inter["b"]["n"] == 10

    def test_backend_spans_and_cache_counters(self):
        from nnstreamer_tpu.backends.xla import XLABackend
        from nnstreamer_tpu.tensor.dtypes import DType
        from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec

        be = XLABackend()
        be.open({"model": lambda x: x * 2.0})
        be.set_input_info(TensorsSpec.of(TensorInfo((1, 4), DType.FLOAT32)))
        tr = Tracer()
        be.tracer = tr
        be.trace_name = "filt"
        try:
            for n in (3, 3, 5):
                x = np.ones((n, 4), np.float32)
                be.invoke_batched((x,), n, [True])
        finally:
            be.close()
        # 3→bucket 4 (miss), 3→bucket 4 (hit), 5→bucket 8 (miss)
        assert be.cache_misses == 2
        assert be.cache_hits == 1
        spans = [(name, label, args) for ph, cat, name, label, ts, dur,
                 args in tr.events() if ph == "X" and cat == "backend"]
        assert len(spans) == 3
        assert all(name == "filt" and label == "invoke_batched"
                   for name, label, _ in spans)
        assert [a["cache"] for _, _, a in spans] == ["miss", "hit", "miss"]
        assert [a["bucket"] for _, _, a in spans] == [4, 4, 8]


class TestReport:
    def test_report_table_and_sections(self, _clean_models):
        _clean_models("sleepy", _sleepy)
        p, runner = _run_traced(
            "videotestsrc width=4 height=4 num-buffers=4 ! tensor_converter "
            "! tensor_transform mode=typecast option=float32 "
            "! tensor_filter framework=custom model=sleepy name=filt "
            "! tensor_sink name=out")
        rep = runner.report()
        assert "element report" in rep
        assert "queue high-water" in rep
        assert "interlatency" in rep
        assert "(sink)" in rep
        for col in ("buffers", "total ms", "q.peak", "p50", "p99"):
            assert col in rep
        # sorted by total proctime: the sleeping filter leads the table
        table_rows = [l for l in rep.splitlines()
                      if l.startswith(("filt", "out", "conv"))]
        assert table_rows and table_rows[0].startswith("filt")

    def test_report_without_tracer_still_has_proctime(self):
        p = parse_launch("videotestsrc width=4 height=4 num-buffers=2 "
                         "! tensor_converter ! tensor_sink name=out")
        runner = PipelineRunner(p).start()
        runner.wait(10)
        runner.stop()
        rep = runner.report()
        assert "element report" in rep
        assert "queue high-water" in rep
        assert "interlatency" not in rep


class TestSchedulerAccounting:
    def test_wait_timeout_chains_pending_error(self, _clean_models):
        def boom(ts):
            raise RuntimeError("model exploded")

        _clean_models("boom", boom, infer_out=lambda s: s)
        # appsrc never ends: the source pump stays alive after the filter
        # fails, so wait() hits the timeout path WITH a pending error —
        # the root cause must surface, not a bare timeout
        p = parse_launch(
            "appsrc name=in dims=4 types=float32 ! "
            "tensor_filter framework=custom model=boom ! tensor_sink")
        runner = PipelineRunner(p).start()
        p.get("in").push(np.zeros((4,), np.float32))
        time.sleep(0.2)
        with pytest.raises(StreamError, match="model exploded"):
            runner.wait(0.5)
        runner.stop()

    def test_teardown_drop_counter(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=40 ! "
            "tensor_converter name=conv ! tensor_sink name=out")
        sink = p.get("out")
        orig = sink.render

        def crawl(buf):
            time.sleep(0.2)
            orig(buf)

        sink.render = crawl
        runner = PipelineRunner(p, queue_capacity=1).start()
        time.sleep(0.5)   # producers are now blocked on the full queue
        runner.stop()
        runner.wait(10)
        st = runner.stats()
        # the aborted put loop counted its lost buffer on the producer
        assert sum(d["dropped"] for d in st.values()) >= 1
        # clean EOS runs never drop (covered by every other test here,
        # asserted once explicitly):
        p2, r2 = _run_traced("videotestsrc width=4 height=4 num-buffers=3 "
                             "! tensor_converter ! tensor_sink")
        assert all(d["dropped"] == 0 for d in r2.stats().values())

    def test_noop_tracer_overhead_smoke(self, _clean_models):
        _clean_models("sleepy", _sleepy)
        desc = ("videotestsrc width=4 height=4 num-buffers=12 ! "
                "tensor_converter "
                "! tensor_transform mode=typecast option=float32 "
                "! tensor_filter framework=custom model=sleepy name=filt "
                "! tensor_sink")

        def proctime(trace):
            p = parse_launch(desc)
            runner = PipelineRunner(p, trace=trace).start()
            runner.wait(30)
            runner.stop()
            return runner.stats()["filt"]["proctime_avg_us"]

        off = proctime(False)
        on = proctime(True)
        # the filter's work is a 10ms sleep: tracing (off OR on) must be
        # invisible at this scale — generous 1.5x bound for CI jitter,
        # the real claim (≤10%) is held by the dyn_batch bench family
        assert off < SLEEP_S * 1e6 * 1.5
        assert on < off * 1.5


class TestDebugCapture:
    def test_capture_bounded_and_extra_stats(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=12 ! "
            "tensor_converter ! "
            "tensor_debug name=dbg capture=true capture-limit=5 ! "
            "tensor_sink name=out")
        runner = PipelineRunner(p).start()
        runner.wait(10)
        runner.stop()
        dbg = p.get("dbg")
        assert len(dbg.lines) == 5            # bounded: oldest dropped
        st = runner.stats()["dbg"]
        assert st["buffers_seen"] == 12
        assert st["captured_lines"] == 5
        # 12 buffer lines + 1 negotiation line, 5 kept
        assert st["capture_dropped"] == 8
        # the deque keeps the newest lines: the (earliest) negotiation
        # line is among the dropped
        assert not any("negotiated" in l for l in dbg.lines)


class TestCLI:
    def test_trace_subcommand_writes_valid_trace(self, tmp_path, capsys):
        from nnstreamer_tpu.__main__ import main

        out = tmp_path / "trace.json"
        rc = main(["trace",
                   "videotestsrc width=4 height=4 num-buffers=3 ! "
                   "tensor_converter ! tensor_sink",
                   "--out", str(out), "--timeout", "30"])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        names = {ev["args"]["name"] for ev in doc["traceEvents"]
                 if ev["ph"] == "M" and ev["name"] == "thread_name"}
        assert len(names) >= 2       # converter + sink tracks at least
        captured = capsys.readouterr()
        assert "element report" in captured.out
        assert "interlatency" in captured.out


# -- distributed tracing (request contexts, child shipping, merge) -----------

from nnstreamer_tpu.runtime.tracing import (  # noqa: E402
    HIST_BOUNDS_S, TRACE_CTX_META, ensure_trace_ctx, get_trace_ctx,
    hop_spans, merge_chrome_traces, stamp_hop)
from nnstreamer_tpu.tensor.buffer import TensorBuffer  # noqa: E402


class TestTraceContext:
    def test_ensure_creates_once_and_reuses_id(self):
        meta = {}
        ctx = ensure_trace_ctx(meta)
        assert len(ctx["id"]) == 16 and ctx["hops"] == []
        # the retry invariant: a re-offered buffer keeps its id
        assert ensure_trace_ctx(meta)["id"] == ctx["id"]
        assert meta[TRACE_CTX_META] is ctx

    def test_get_never_creates(self):
        meta = {}
        assert get_trace_ctx(meta) is None
        assert meta == {}
        assert get_trace_ctx(None) is None
        assert get_trace_ctx({"_trace_ctx": "junk"}) is None

    def test_stamp_is_noop_without_ctx(self):
        # the tracer-off hot path: stamping sites run unguarded on
        # every frame, so without a context they must not mutate meta,
        # allocate a context, or return a record
        meta = {"pts": 3}
        assert stamp_hop(meta, "admit") is None
        assert meta == {"pts": 3}
        assert stamp_hop(None, "admit") is None
        assert stamp_hop("not-a-dict", "admit") is None

    def test_stamp_appends_with_extras(self):
        meta = {}
        ensure_trace_ctx(meta)
        rec = stamp_hop(meta, "dispatch", wid=1, attempt=0)
        assert rec["hop"] == "dispatch" and rec["wid"] == 1
        assert rec["pid"] > 0 and rec["t"] > 0
        assert get_trace_ctx(meta)["hops"] == [rec]

    def test_hop_spans_decomposition(self):
        hops = [{"hop": h, "t": t} for h, t in (
            ("client_send", 1.000), ("admit", 1.001), ("dequeue", 1.003),
            ("dispatch", 1.004), ("worker_recv", 1.010),
            ("worker_done", 1.030), ("reply", 1.031))]
        s = hop_spans(hops)
        assert s["admission_wait_ms"] == pytest.approx(2.0, abs=1e-6)
        assert s["route_ms"] == pytest.approx(1.0, abs=1e-6)
        assert s["worker_queue_ms"] == pytest.approx(6.0, abs=1e-6)
        assert s["service_ms"] == pytest.approx(20.0, abs=1e-6)
        assert s["reply_ms"] == pytest.approx(1.0, abs=1e-6)
        assert s["total_ms"] == pytest.approx(31.0, abs=1e-6)
        assert "retries" not in s and "redeliveries" not in s

    def test_hop_spans_redelivery_last_attempt_wins(self):
        hops = [{"hop": h, "t": t} for h, t in (
            ("client_send", 0.0), ("client_send", 0.050),   # one retry
            ("admit", 0.051), ("dequeue", 0.052),
            ("dispatch", 0.053), ("reoffer", 0.080),        # dead worker
            ("dispatch", 0.081), ("worker_recv", 0.082),
            ("worker_done", 0.092), ("reply", 0.093))]
        s = hop_spans(hops)
        assert s["retries"] == 1
        assert s["redeliveries"] == 1
        # stage math uses the LAST dispatch, not the dead one
        assert s["worker_queue_ms"] == pytest.approx(1.0, abs=1e-6)

    def test_hop_spans_lists_hosts_in_first_dispatch_order(self):
        """ISSUE 12 satellite: a cross-host redelivered request keeps
        ONE trace whose dispatch hops name every host it touched —
        the span view surfaces them in first-dispatch order."""
        hops = [{"hop": "client_send", "t": 0.0},
                {"hop": "admit", "t": 0.001},
                {"hop": "dispatch", "t": 0.002, "host": "hB"},
                {"hop": "reoffer", "t": 0.050, "cause": "host_lost"},
                {"hop": "dispatch", "t": 0.051, "host": "hA"},
                # second attempt on the same host must not duplicate
                {"hop": "dispatch", "t": 0.052, "host": "hA"},
                {"hop": "worker_recv", "t": 0.053},
                {"hop": "worker_done", "t": 0.060},
                {"hop": "reply", "t": 0.061}]
        s = hop_spans(hops)
        assert s["hosts"] == ["hB", "hA"]
        assert s["redeliveries"] == 1
        # single-host (or pool-local, no host key) traces stay clean
        assert "hosts" not in hop_spans(
            [{"hop": "dispatch", "t": 0.0}, {"hop": "reply", "t": 0.1}])

    def test_wire_codec_carries_nested_ctx(self):
        from nnstreamer_tpu.edge.wire import decode_buffer, encode_buffer

        buf = TensorBuffer.of(np.ones((4,), np.float32), pts=7)
        ensure_trace_ctx(buf.meta)
        stamp_hop(buf.meta, "client_send", pts=7)
        out, _ = decode_buffer(encode_buffer(buf))
        ctx = get_trace_ctx(out.meta)
        assert ctx is not None
        assert ctx["id"] == get_trace_ctx(buf.meta)["id"]
        assert ctx["hops"][0]["hop"] == "client_send"


class TestChildShipping:
    def _child_with_work(self, n=5):
        child = Tracer()
        child.enable_shipping()
        buf = TensorBuffer.of(np.ones((2,), np.float32))
        t0 = time.perf_counter()
        for i in range(n):
            child.record_process("echo", buf, t0 + i, t0 + i + 0.001)
        return child

    def test_ship_delta_then_quiet_returns_none(self):
        child = self._child_with_work()
        delta = child.ship_delta()
        assert delta["events_total_delta"] == 5
        assert delta["hists"]["echo"]["count"] == 5
        assert child.ship_delta() is None      # nothing new

    def test_deltas_not_cumulative(self):
        child = self._child_with_work(3)
        child.ship_delta()
        buf = TensorBuffer.of(np.ones((2,), np.float32))
        t0 = time.perf_counter()
        child.record_process("echo", buf, t0, t0 + 0.001)
        d2 = child.ship_delta()
        assert d2["events_total_delta"] == 1
        assert d2["hists"]["echo"]["count"] == 1

    def test_parent_merge_namespaces_and_counts(self):
        parent = Tracer()
        child = self._child_with_work(4)
        parent.ingest_child(0, 111, child.ship_delta(), label="pool-w0")
        assert parent.hists()["w0/echo"]["count"] == 4
        kids = parent.children()
        assert kids[0]["pid"] == 111 and kids[0]["events_total"] == 4
        assert kids[0]["events_dropped"] == 0
        assert parent.summary()["children"]["0"]["label"] == "pool-w0"

    def test_restart_resumes_totals_monotone(self):
        # a replacement worker ships deltas from zero; parent totals
        # must keep rising, never reset
        parent = Tracer()
        child = self._child_with_work(3)
        parent.ingest_child(0, 111, child.ship_delta())
        total_before = parent.total_events
        replacement = self._child_with_work(2)     # fresh process
        parent.ingest_child(0, 222, replacement.ship_delta())
        assert parent.total_events == total_before + 2
        assert parent.hists()["w0/echo"]["count"] == 5
        assert parent.children()[0]["pid"] == 222  # new pid tracked

    def test_clock_offset_applied_to_child_events(self):
        parent = Tracer()
        buf = TensorBuffer.of(np.ones((2,), np.float32))
        t0 = time.perf_counter()
        parent.record_process("router", buf, t0, t0 + 1e-4)
        child = self._child_with_work(1)
        parent.ingest_child(0, 111, child.ship_delta(), offset_s=100.0)
        doc = parent.to_chrome_trace("p")
        parent_spans = [e for e in doc["traceEvents"]
                        if e.get("ph") == "X" and e.get("pid") == 0]
        child_spans = [e for e in doc["traceEvents"]
                       if e.get("ph") == "X" and e.get("pid") == 1]
        assert parent_spans and child_spans
        # Chrome ts is µs (normalized to trace start): the 100s skew
        # correction must push the child span ~100s past the parent's
        gap_us = child_spans[0]["ts"] - parent_spans[0]["ts"]
        assert gap_us >= 99.0 * 1e6

    def test_ring_wrap_keeps_child_drop_accounting_exact(self):
        # satellite: child batches arriving after the PARENT ring
        # wrapped must keep events_dropped and per-element counters
        # exact — the per-child ring has its own drop budget
        parent = Tracer(max_events=64)     # child rings: max(1024, 16)
        # wrap the parent's own ring completely
        buf = TensorBuffer.of(np.ones((2,), np.float32))
        t0 = time.perf_counter()
        for i in range(200):
            parent.record_process("parent_el", buf, t0, t0 + 1e-4)
        assert parent.events_dropped > 0
        parent_dropped = parent.events_dropped
        # now a child ships MORE events than its parent-side ring holds
        child = Tracer()
        child.enable_shipping()
        for i in range(1500):
            child.record_process("echo", buf, t0, t0 + 1e-4)
        parent.ingest_child(0, 111, child.ship_delta())
        kids = parent.children()
        assert kids[0]["events_total"] == 1500
        assert kids[0]["events_kept"] == 1024
        assert kids[0]["events_dropped"] == 1500 - 1024
        # pool-level totals: monotone counter and exact drop sum
        assert parent.total_events == 200 + 1500
        assert parent.events_dropped == parent_dropped + (1500 - 1024)
        # histogram counters survive wrap exactly (kept-whole, not ring)
        assert parent.hists()["w0/echo"]["count"] == 1500

    def test_child_ring_wrap_reported_by_child(self):
        # the CHILD's own ring can wrap between ships: its self-reported
        # drop delta must flow into the parent's accounting
        parent = Tracer()
        child = Tracer(max_events=32)
        child.enable_shipping()
        buf = TensorBuffer.of(np.ones((2,), np.float32))
        t0 = time.perf_counter()
        for i in range(100):
            child.record_process("echo", buf, t0, t0 + 1e-4)
        delta = child.ship_delta()
        assert delta["events_dropped_delta"] == 100 - 32
        parent.ingest_child(0, 111, delta)
        assert parent.children()[0]["events_dropped"] == 100 - 32
        assert parent.events_dropped >= 100 - 32

    def test_requests_merge_with_offset(self):
        parent = Tracer()
        child = Tracer()
        child.enable_shipping()
        hops = [{"hop": "worker_recv", "t": 1.0},
                {"hop": "worker_done", "t": 1.002}]
        child.record_request("svc", "abcd1234abcd1234", hops, 1.002)
        parent.ingest_child(1, 99, child.ship_delta(), offset_s=2.0)
        reqs = parent.requests()
        assert len(reqs) == 1
        name, tid, t, _, _ = reqs[0]
        assert name == "w1/svc" and tid == "abcd1234abcd1234"
        assert t == pytest.approx(3.002)


class TestMergeChromeTraces:
    def test_pid_remap_no_collisions(self):
        def mkdoc():
            tr = Tracer()
            child = Tracer()
            child.enable_shipping()
            buf = TensorBuffer.of(np.ones((2,), np.float32))
            t0 = time.perf_counter()
            child.record_process("echo", buf, t0, t0 + 1e-4)
            tr.record_process("router", buf, t0, t0 + 1e-4)
            tr.ingest_child(0, 1, child.ship_delta())
            return tr.to_chrome_trace("p")

        a, b = mkdoc(), mkdoc()
        merged = merge_chrome_traces([a, b], labels=["runA", "runB"])
        pids = {e["pid"] for e in merged["traceEvents"]}
        names = {e["pid"]: e["args"]["name"]
                 for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        assert len(pids) == 4            # 2 docs x (parent + 1 worker)
        assert sum(1 for n in names.values()
                   if n.startswith("runA/")) == 2
        assert sum(1 for n in names.values()
                   if n.startswith("runB/")) == 2
        total = len(a["traceEvents"]) + len(b["traceEvents"])
        assert len(merged["traceEvents"]) == total


class TestHistBounds:
    def test_bounds_cover_service_range(self):
        assert HIST_BOUNDS_S[0] == pytest.approx(1e-5)
        assert HIST_BOUNDS_S[-1] == pytest.approx(10.0)
        assert list(HIST_BOUNDS_S) == sorted(HIST_BOUNDS_S)
