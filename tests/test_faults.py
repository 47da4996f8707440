"""Fault-tolerance layer tests (docs/robustness.md): error policies,
tensor_fault injection, watchdog, circuit breaker, and the pre-existing
error paths the layer formalizes (source death, element death, wait()
root-cause chaining, repo slot overflow, upstream-event handler errors).

Everything runs on the fake (custom) backend / synthetic streams — no
models, no device."""

import pickle
import queue as _queue
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core import errors as errors_mod
from nnstreamer_tpu import (
    Pipeline,
    PipelineRunner,
    TensorBuffer,
    parse_launch,
    register_custom_easy,
    run_pipeline,
)
from nnstreamer_tpu.backends.base import CircuitBreaker
from nnstreamer_tpu.backends.custom import unregister_custom_easy
from nnstreamer_tpu.core.errors import (
    CircuitOpenError,
    ErrorPolicy,
    FaultInjected,
    PipelineError,
    StreamError,
    WatchdogStall,
)
from nnstreamer_tpu.elements import TensorFault, TensorFilter, TensorSink
from nnstreamer_tpu.elements.repo import REPO, TensorRepoSink
from nnstreamer_tpu.elements.sources import AppSrc
from nnstreamer_tpu.graph.pipeline import Element, SourceElement
from nnstreamer_tpu.tensor.info import TensorsSpec


@pytest.fixture(autouse=True)
def _clean_models():
    names = []

    def reg(name, *a, **kw):
        names.append(name)
        return register_custom_easy(name, *a, **kw)

    yield reg
    for n in names:
        unregister_custom_easy(n)


def _wait_for(cond, timeout=10.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timed out waiting: {what}"
        time.sleep(0.01)


# -- error-policy grammar ----------------------------------------------------

class TestErrorPolicyParse:
    def test_kinds(self):
        assert ErrorPolicy.parse("fail").kind == "fail"
        assert ErrorPolicy.parse("skip").kind == "skip"
        assert ErrorPolicy.parse("degrade").kind == "degrade"

    def test_retry(self):
        p = ErrorPolicy.parse("retry:3")
        assert (p.kind, p.retries, p.backoff_ms) == ("retry", 3, 10.0)
        p = ErrorPolicy.parse("retry:2:5.5")
        assert (p.retries, p.backoff_ms) == (2, 5.5)

    def test_roundtrip_str(self):
        for s in ("fail", "skip", "degrade", "retry:4:25"):
            assert str(ErrorPolicy.parse(s)) == s

    @pytest.mark.parametrize("bad", ["", "nope", "retry", "retry:0",
                                     "retry:x", "retry:1:-5"])
    def test_rejects(self, bad):
        with pytest.raises(ValueError, match="error-policy"):
            ErrorPolicy.parse(bad)

    def test_element_property(self):
        f = TensorFault(error_policy="retry:2")
        assert f.error_policy.kind == "retry"
        # default stays the fail-fast contract
        assert TensorFault().error_policy.kind == "fail"

    def test_unknown_prop_message_lists_common(self):
        with pytest.raises(PipelineError, match="error-policy"):
            TensorFault(no_such_prop=1)


# -- pre-existing error paths (now under test) -------------------------------

class _BoomSrc(SourceElement):
    """Source that dies after its first buffer (mid-generate failure)."""

    ELEMENT_NAME = "boom_src"

    def output_spec(self):
        return TensorsSpec.from_strings("2:2", "float32")

    def generate(self):
        yield TensorBuffer.of(np.zeros((2, 2), np.float32))
        raise RuntimeError("source exploded mid-stream")


class TestExistingErrorPaths:
    def test_source_raises_mid_generate(self):
        p = Pipeline("boom")
        src = p.add(_BoomSrc(name="src"))
        sink = p.add(TensorSink(name="out"))
        p.link(src, sink)
        with pytest.raises(StreamError, match="source exploded") as ei:
            run_pipeline(p, timeout=10)
        assert isinstance(ei.value.__cause__, RuntimeError)

    def test_element_raises_on_frame_k_fail_fast(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=10 ! "
            "tensor_converter ! tensor_fault mode=raise period=3 ! "
            "tensor_sink name=out")
        with pytest.raises(StreamError, match="injected failure") as ei:
            run_pipeline(p, timeout=10)
        assert isinstance(ei.value.__cause__, FaultInjected)
        # frames past the failure never arrive
        assert len(p.get("out").results) <= 2

    def test_wait_timeout_chains_root_cause(self, _clean_models):
        _clean_models("slowmodel",
                      lambda ts: (time.sleep(3.0), ts)[1])
        # two disjoint chains: one dies instantly, one is stuck in a
        # non-interruptible invoke — wait(timeout) must surface the
        # original error, not a bare timeout
        p = Pipeline("stuck")
        s1 = p.add(AppSrc(name="s1", spec=TensorsSpec.from_strings(
            "2:2", "float32")))
        flt = p.add(TensorFault(name="boom", mode="raise", period=1))
        k1 = p.add(TensorSink(name="k1"))
        p.link(s1, flt)
        p.link(flt, k1)
        s2 = p.add(AppSrc(name="s2", spec=TensorsSpec.from_strings(
            "2:2", "float32")))
        slow = p.add(TensorFilter(name="slow", framework="custom",
                                  model="slowmodel"))
        k2 = p.add(TensorSink(name="k2"))
        p.link(s2, slow)
        p.link(slow, k2)
        runner = PipelineRunner(p).start()
        frame = TensorBuffer.of(np.zeros((2, 2), np.float32))
        s2.push(frame)          # slow branch enters its 3s invoke
        time.sleep(0.3)
        s1.push(frame)          # boom branch fails immediately
        try:
            with pytest.raises(StreamError,
                               match="did not finish within") as ei:
                runner.wait(timeout=1.0)
            assert "injected failure" in str(ei.value)
            assert isinstance(ei.value.__cause__, FaultInjected)
        finally:
            runner.stop()
        time.sleep(2.5)         # let the sleeping invoke drain (daemon)


# -- skip / retry / degrade --------------------------------------------------

class TestPolicies:
    def test_skip_conservation(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=20 ! "
            "tensor_converter ! tensor_fault name=flt mode=raise period=4 "
            "error-policy=skip ! tensor_sink name=out")
        runner = PipelineRunner(p)
        runner.run(timeout=15)
        st = runner.stats()["flt"]
        sink = p.get("out")
        assert sink.eos.is_set()
        assert st["skipped"] == 5          # frames 1,5,9,... wait: 4,8,...
        assert st["errors"] == st["skipped"]
        assert len(sink.results) + st["skipped"] == 20
        assert st["dropped"] == 0

    def test_retry_recovers_transient_failure(self, _clean_models):
        calls = {"n": 0}

        def flaky(ts):
            calls["n"] += 1
            if calls["n"] == 3:            # fail frame 3, first attempt only
                raise RuntimeError("transient")
            return ts

        _clean_models("flaky_once", flaky)
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=5 ! "
            "tensor_converter ! tensor_transform mode=typecast "
            "option=float32 ! tensor_filter name=f framework=custom "
            "model=flaky_once error-policy=retry:2:1 ! tensor_sink "
            "name=out")
        runner = PipelineRunner(p)
        runner.run(timeout=15)
        st = runner.stats()["f"]
        assert len(p.get("out").results) == 5   # nothing lost
        assert st["errors"] == 1
        assert st["retries"] == 1
        assert st["skipped"] == 0

    def test_retry_exhausted_falls_back_to_skip(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=3 ! "
            "tensor_converter ! tensor_fault name=flt mode=raise "
            "probability=1.0 error-policy=retry:2:1 ! tensor_sink name=out")
        runner = PipelineRunner(p)
        runner.run(timeout=15)
        st = runner.stats()["flt"]
        assert len(p.get("out").results) == 0
        assert st["skipped"] == 3              # every buffer abandoned
        assert st["retries"] == 6              # 2 retries per buffer
        assert st["errors"] == 9               # 3 attempts per buffer
        assert p.get("out").eos.is_set()

    def test_degrade_routes_input_to_fallback_pad(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=6 ! "
            "tensor_converter ! tensor_fault name=flt mode=raise period=2 "
            "error-policy=degrade flt.src_0 ! tensor_sink name=ok "
            "flt.src_1 ! tensor_sink name=fb")
        runner = PipelineRunner(p)
        runner.run(timeout=15)
        ok, fb = p.get("ok"), p.get("fb")
        assert len(ok.results) == 3
        assert len(fb.results) == 3            # raw inputs, rerouted
        st = runner.stats()["flt"]
        assert st["degraded"] == 3
        # fallback carries the *unprocessed* input spec
        assert fb.results[0].tensors[0].dtype == np.uint8

    def test_degrade_requires_linked_fallback_pad(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=2 ! "
            "tensor_converter ! tensor_fault mode=raise period=2 "
            "error-policy=degrade ! tensor_sink")
        with pytest.raises(PipelineError, match="fallback"):
            run_pipeline(p, timeout=10)

    def test_policy_on_source_rejected(self):
        p = parse_launch(
            "videotestsrc num-buffers=2 error-policy=skip ! "
            "tensor_converter ! tensor_sink")
        with pytest.raises(PipelineError, match="source"):
            run_pipeline(p, timeout=10)


# -- acceptance: 5% chaos to EOS with exact conservation ---------------------

class TestChaosAcceptance:
    @pytest.mark.parametrize("policy", ["skip", "retry:3:1"])
    def test_five_percent_raise_completes_to_eos(self, policy):
        p = parse_launch(
            f"videotestsrc width=4 height=4 num-buffers=100 ! "
            f"tensor_converter ! tensor_fault name=flt mode=raise "
            f"probability=0.05 seed=7 error-policy={policy} ! "
            f"tensor_sink name=out")
        runner = PipelineRunner(p)
        runner.run(timeout=30)
        sink = p.get("out")
        st = runner.stats()["flt"]
        assert sink.eos.is_set()
        # conservation: emitted + skipped + dropped == generated
        assert len(sink.results) + st["skipped"] + st["dropped"] == 100
        if policy == "skip":
            assert st["errors"] > 0            # seed 7 does inject faults
            assert st["skipped"] == st["errors"]

    def test_escalation_on_poison_stream(self):
        # no other processing element in the chain: the counter resets on
        # ANY successful process() in the pipeline, so e.g. a converter
        # between src and fault would race the escalation
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=20 ! "
            "tensor_fault mode=raise probability=1.0 "
            "error-policy=skip ! tensor_sink")
        runner = PipelineRunner(p, max_consecutive_errors=5)
        with pytest.raises(StreamError, match="consecutive errors"):
            runner.run(timeout=15)


# -- tensor_fault element ----------------------------------------------------

class TestTensorFault:
    def test_seeded_probability_is_deterministic(self):
        def run_once():
            p = parse_launch(
                "videotestsrc width=4 height=4 num-buffers=50 ! "
                "tensor_converter ! tensor_fault name=flt mode=drop "
                "probability=0.2 seed=42 ! tensor_sink name=out")
            run_pipeline(p, timeout=15)
            return len(p.get("out").results), p.get("flt").injected

        a, b = run_once(), run_once()
        assert a == b
        assert a[1] > 0 and a[0] + a[1] == 50

    def test_max_faults_cap(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=10 ! "
            "tensor_converter ! tensor_fault name=flt mode=drop period=2 "
            "max-faults=2 ! tensor_sink name=out")
        run_pipeline(p, timeout=15)
        assert p.get("flt").injected == 2
        assert len(p.get("out").results) == 8

    def test_corrupt_shape_breaks_downstream(self, _clean_models):
        def strict(ts):
            if ts[0].ndim != 4:        # (1, 4, 4, 3) from the converter
                raise RuntimeError(f"unexpected shape {ts[0].shape}")
            return ts

        _clean_models("strict_shape", strict, infer_out=lambda s: s)
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=5 ! "
            "tensor_converter ! tensor_transform mode=typecast "
            "option=float32 ! tensor_fault mode=corrupt-shape period=2 ! "
            "tensor_filter framework=custom model=strict_shape ! "
            "tensor_sink name=out")
        with pytest.raises(StreamError):
            run_pipeline(p, timeout=15)

    def test_bad_mode_rejected_at_negotiation(self):
        p = parse_launch(
            "videotestsrc num-buffers=1 ! tensor_converter ! "
            "tensor_fault mode=wat ! tensor_sink")
        with pytest.raises(Exception, match="unknown mode"):
            run_pipeline(p, timeout=10)


# -- watchdog ----------------------------------------------------------------

class TestWatchdog:
    def test_flags_stalled_element_within_2x_budget(self):
        # each process() parks ~1.1s; budget 0.5s → the watchdog must
        # flag the stall while the call is still in flight (≈2x budget)
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=2 ! "
            "tensor_converter ! tensor_fault name=flt mode=delay "
            "delay-ms=1100 period=1 ! tensor_sink name=out")
        runner = PipelineRunner(p, stall_budget_s=0.5)
        runner.run(timeout=30)
        st = runner.stats()["flt"]
        assert st["watchdog_warnings"] >= 1
        assert p.get("out").eos.is_set()       # warn-only: run completes

    def test_no_false_positives_on_fast_pipeline(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=20 ! "
            "tensor_converter ! tensor_sink name=out")
        runner = PipelineRunner(p, stall_budget_s=0.5)
        runner.run(timeout=15)
        assert all(d["watchdog_warnings"] == 0
                   for d in runner.stats().values())

    def test_action_fail_tears_down_with_watchdog_stall(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=4 ! "
            "tensor_converter ! tensor_fault mode=delay delay-ms=30000 "
            "period=1 ! tensor_sink")
        runner = PipelineRunner(p, stall_budget_s=0.3,
                                watchdog_action="fail")
        with pytest.raises(StreamError, match="stall budget") as ei:
            runner.run(timeout=30)
        assert isinstance(ei.value.__cause__, WatchdogStall)

    def test_bad_action_rejected(self):
        p = parse_launch("videotestsrc num-buffers=1 ! tensor_converter "
                         "! tensor_sink")
        with pytest.raises(PipelineError, match="watchdog_action"):
            PipelineRunner(p, watchdog_action="explode")


# -- circuit breaker ---------------------------------------------------------

class TestCircuitBreakerUnit:
    def test_state_machine_with_fake_clock(self):
        clk = [0.0]
        b = CircuitBreaker(threshold=2, cooldown_s=5.0,
                           clock=lambda: clk[0])
        assert b.state == "closed"
        b.guard("t")                       # closed: no-op
        b.record_failure()
        assert b.state == "closed"         # below threshold
        b.record_failure()
        assert b.state == "open"
        assert b.opened_count == 1
        # open + cooling: guard short-circuits without touching anything
        with pytest.raises(CircuitOpenError, match="circuit open"):
            b.guard("t")
        assert b.short_circuited == 1
        # cooldown elapsed: next guard half-opens (the probe)
        clk[0] = 6.0
        b.guard("t")
        assert b.state == "half_open"
        assert b.probes == 1
        # probe fails → re-open with a fresh cooldown
        b.record_failure()
        assert b.state == "open" and b.opened_count == 2
        clk[0] = 12.0
        b.guard("t")
        b.record_success()                 # probe succeeds → recovery
        assert b.state == "closed"
        assert b.recoveries == 1
        # recovered: failures start from zero again
        b.record_failure()
        assert b.state == "closed"

    def test_threshold_validated(self):
        with pytest.raises(ValueError, match="threshold"):
            CircuitBreaker(threshold=0, cooldown_s=1.0)

    def test_stats_shape(self):
        b = CircuitBreaker(threshold=1, cooldown_s=1.0)
        s = b.stats()
        assert s["state"] == "closed"
        assert set(s) == {"state", "consecutive_failures", "opened",
                          "short_circuited", "probes", "recoveries"}


class TestCircuitBreakerInPipeline:
    def test_open_fallback_probe_recover(self, _clean_models):
        calls = {"n": 0}
        fail = {"on": True}

        def backend_fn(ts):
            calls["n"] += 1
            if fail["on"]:
                raise RuntimeError("backend down")
            return ts

        # infer_out skips the zero-probe at negotiation (the backend is
        # "down" from the start, but negotiation must still succeed)
        _clean_models("breaker_model", backend_fn, infer_out=lambda s: s)
        clk = [0.0]
        p = Pipeline("breaker")
        src = p.add(AppSrc(name="src", spec=TensorsSpec.from_strings(
            "2:2", "float32")))
        flt = p.add(TensorFilter(name="f", framework="custom",
                                 model="breaker_model",
                                 error_policy="skip"))
        sink = p.add(TensorSink(name="out"))
        p.link(src, flt)
        p.link(flt, sink)
        # injected clock makes cooldown fully deterministic
        flt._breaker = CircuitBreaker(threshold=2, cooldown_s=10.0,
                                      clock=lambda: clk[0])
        runner = PipelineRunner(p).start()
        frame = TensorBuffer.of(np.ones((2, 2), np.float32))
        try:
            st = lambda: runner.stats()["f"]
            src.push(frame)                # failure 1 (invoked)
            _wait_for(lambda: st()["errors"] == 1, what="first failure")
            src.push(frame)                # failure 2 → circuit opens
            _wait_for(lambda: st()["errors"] == 2, what="circuit open")
            assert flt._breaker.state == "open"
            assert calls["n"] == 2
            src.push(frame)                # short-circuited, backend idle
            _wait_for(lambda: st()["errors"] == 3, what="short circuit")
            assert calls["n"] == 2         # backend NOT touched
            assert flt._breaker.short_circuited == 1
            # heal the backend, let the cooldown elapse → probe recovers
            fail["on"] = False
            clk[0] = 11.0
            src.push(frame)
            src.end()
            runner.wait(timeout=10)
        finally:
            runner.stop()
        assert len(sink.results) == 1      # the probe frame came through
        d = runner.stats()["f"]
        assert d["skipped"] == 3
        assert d["breaker_state"] == "closed"
        assert d["breaker_opened"] == 1
        assert d["breaker_probes"] == 1
        assert d["breaker_recoveries"] == 1
        assert d["backend_invoke_failures"] == 2

    def test_breaker_props_build_breaker(self, _clean_models):
        _clean_models("ok_model", lambda ts: ts)
        p = parse_launch(
            "appsrc name=src dims=2:2 types=float32 ! "
            "tensor_filter name=f framework=custom model=ok_model "
            "breaker-threshold=3 breaker-cooldown-ms=250 ! "
            "tensor_sink name=out")
        runner = PipelineRunner(p).start()
        try:
            flt = p.get("f")
            assert flt._breaker is not None
            assert flt._breaker.threshold == 3
            assert flt._breaker.cooldown_s == 0.25
            p.get("src").end()
            runner.wait(timeout=10)
        finally:
            runner.stop()


# -- repo slot overflow (stop-aware put) -------------------------------------

class TestRepoSlot:
    def test_full_slot_raises_descriptive_stream_error(self):
        REPO.reset()
        sink = TensorRepoSink(slot=77, put_timeout=0.4)
        q = REPO.slot(77)
        buf = TensorBuffer.of(np.zeros((2,), np.float32))
        while True:                        # fill to capacity (16)
            try:
                q.put_nowait(buf)
            except _queue.Full:
                break
        t0 = time.monotonic()
        with pytest.raises(StreamError, match="slot 77"):
            sink.render(buf)
        assert time.monotonic() - t0 < 5.0  # honored put_timeout, not 10s
        REPO.reset()

    def test_teardown_aborts_blocked_put(self):
        REPO.reset()
        sink = TensorRepoSink(slot=78, put_timeout=30.0)
        evt = threading.Event()
        sink._stop_evt = evt
        q = REPO.slot(78)
        buf = TensorBuffer.of(np.zeros((2,), np.float32))
        while True:
            try:
                q.put_nowait(buf)
            except _queue.Full:
                break
        evt.set()
        t0 = time.monotonic()
        with pytest.raises(StreamError, match="stopping"):
            sink.render(buf)
        assert time.monotonic() - t0 < 5.0  # did not ride out 30s
        REPO.reset()


# -- upstream event errors ---------------------------------------------------

class _BadHandler(Element):
    ELEMENT_NAME = "bad_handler"

    def negotiate(self, in_specs):
        return [in_specs[0]]

    def process(self, pad, buf):
        return [(0, buf)]

    def handle_upstream_event(self, event):
        raise RuntimeError("handler exploded")


class TestUpstreamEventErrors:
    def test_broken_handler_does_not_consume_event(self):
        p = Pipeline("events")
        src = p.add(AppSrc(name="src", spec=TensorsSpec.from_strings(
            "2:2", "float32")))
        mid = p.add(_BadHandler(name="mid"))
        sink = p.add(TensorSink(name="out"))
        p.link(src, mid)
        p.link(mid, sink)
        runner = PipelineRunner(p).start()
        try:
            # QoS event from the sink must walk PAST the broken handler
            # and still reach (and be consumed by) the source
            sink.post_upstream_event(
                {"type": "qos", "min_interval_ns": 12345})
            assert src.qos_min_interval_ns == 12345
            assert runner.stats()["mid"]["event_errors"] == 1
            src.end()
            runner.wait(timeout=10)
        finally:
            runner.stop()


# -- error pickling (worker-pool wire contract) ------------------------------

# serving/pool.py ships exceptions across process boundaries; every
# public error class must survive pickle exactly — args, message, and
# any extra instance state (ServerBusyError.retry_after_ms etc.)
_ERR_INSTANCES = [
    errors_mod.NNStreamerTPUError("base"),
    errors_mod.ConfigError("bad [runtime] key: workers"),
    errors_mod.NegotiationError("dims mismatch 4:1 vs 8:1"),
    errors_mod.PipelineError("unbalanced tee"),
    errors_mod.BackendError("xla open failed"),
    errors_mod.SegmentStageError("conv0", ValueError("bad trace")),
    errors_mod.WindowBuildError("4-frame window could not be built"),
    errors_mod.ChipLeaseError("2 device workers but 1 chip(s) leased"),
    errors_mod.StreamError("flow error"),
    errors_mod.ServerBusyError(
        "server busy", queue_depth=17, retry_after_ms=12.5,
        cause="worker_lost", pts=42),
    errors_mod.FaultInjected("injected at pts=3"),
    errors_mod.WatchdogStall("element x stalled 2.0s"),
    errors_mod.CircuitOpenError("breaker open, 3 failures"),
]


class TestErrorPickling:
    @pytest.mark.parametrize(
        "exc", _ERR_INSTANCES, ids=lambda e: type(e).__name__)
    def test_round_trip_preserves_type_args_and_state(self, exc):
        back = pickle.loads(pickle.dumps(exc))
        assert type(back) is type(exc)
        assert back.args == exc.args
        assert str(back) == str(exc)
        state = {k: v for k, v in exc.__dict__.items()}
        assert {k: str(v) if isinstance(v, BaseException) else v
                for k, v in back.__dict__.items()} == \
               {k: str(v) if isinstance(v, BaseException) else v
                for k, v in state.items()}

    def test_every_public_error_class_is_covered(self):
        # a new error class must be added to _ERR_INSTANCES above, or
        # it ships without a pickling guarantee
        public = {
            obj for name, obj in vars(errors_mod).items()
            if isinstance(obj, type)
            and issubclass(obj, Exception)
            and not name.startswith("_")
        }
        covered = {type(e) for e in _ERR_INSTANCES}
        assert public == covered, (
            f"uncovered: {public - covered}, stale: {covered - public}")

    def test_rich_state_survives(self):
        e = errors_mod.ServerBusyError(
            "busy", queue_depth=9, retry_after_ms=7.0,
            cause="shutdown", pts=5)
        back = pickle.loads(pickle.dumps(e))
        assert (back.queue_depth, back.retry_after_ms,
                back.cause, back.pts) == (9, 7.0, "shutdown", 5)
        e2 = errors_mod.SegmentStageError("head", KeyError("w"))
        back2 = pickle.loads(pickle.dumps(e2))
        assert back2.member == "head"
        assert "head" in str(back2)
