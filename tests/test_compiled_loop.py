"""Compiled steady-state loop (runtime/compiled_loop.py + the
scheduler's window path, ISSUE 20): detector/signature/ledger units,
the full entry/bail matrix (shape change, window error, pending swap,
armed timer, EOS drain) driven through a real PipelineRunner with a
deterministic window-capable element, bit-parity of compiled-loop mode
vs per-frame mode (both the scheduler plumbing and the backend's
lax.scan window against per-frame invokes), and the paged-LLM decode
window's token parity.

Determinism note: each scenario pushes its whole trace (and EOS) into
AppSrc *before* the runner starts and gives the element a process()
sleep long enough that the source pump finishes enqueueing while the
first frame is in flight — so window collection always sees the full
queue and the bail points land exactly where the trace puts them.
"""

import time

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu.elements.sinks import TensorSink
from nnstreamer_tpu.elements.sources import AppSrc
from nnstreamer_tpu.core.errors import StreamError, WindowBuildError
from nnstreamer_tpu.graph.pipeline import Element
from nnstreamer_tpu.runtime.compiled_loop import (
    BAIL_CAUSES, LoopStats, SteadyStateDetector, frame_signature)
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.tensor.dtypes import DType
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec


# -- pure units ---------------------------------------------------------------

class TestFrameSignature:
    def test_shape_dtype_identity(self):
        a = TensorBuffer.of(np.ones((2, 3), np.float32))
        b = TensorBuffer.of(np.zeros((2, 3), np.float32))
        c = TensorBuffer.of(np.ones((2, 4), np.float32))
        d = TensorBuffer.of(np.ones((2, 3), np.int32))
        assert frame_signature(a) == frame_signature(b)   # values ignored
        assert frame_signature(a) != frame_signature(c)   # shape matters
        assert frame_signature(a) != frame_signature(d)   # dtype matters

    def test_dyn_batch_count_is_part_of_identity(self):
        x = np.ones((4, 2), np.float32)
        a = TensorBuffer.of(x)
        b = TensorBuffer.of(x)
        b.meta["dyn_batch"] = {"n": 3}
        c = TensorBuffer.of(x)
        c.meta["dyn_batch"] = {"n": 2}
        assert frame_signature(a) != frame_signature(b)
        assert frame_signature(b) != frame_signature(c)

    def test_non_tensor_payload_stays_per_frame(self):
        assert frame_signature(object()) is None


class TestDetector:
    def test_arms_after_streak_and_resets_on_divergence(self):
        det = SteadyStateDetector(arm_after=3)
        sig_a = (((2, 3), "float32"),)
        sig_b = (((2, 4), "float32"),)
        assert [det.observe(sig_a) for _ in range(3)] == \
            [False, False, True]
        assert det.armed
        assert not det.observe(sig_b)        # divergence restarts streak
        assert not det.armed
        assert not det.observe(sig_b)
        assert det.observe(sig_b)            # re-arms on the new shape
        det.reset()
        assert not det.armed

    def test_none_signature_disarms(self):
        det = SteadyStateDetector(arm_after=1)
        assert det.observe((((1,), "f32"),))
        assert not det.observe(None)
        assert not det.armed


class TestLoopStats:
    def test_ledger_snapshot(self):
        ls = LoopStats()
        ls.entries += 2
        ls.steps += 9
        ls.bail("eos")
        ls.bail("shape")
        ls.bail("shape")
        snap = ls.snapshot()
        assert snap == {"loop_entries": 2, "compiled_steps": 9,
                        "loop_bails": {"eos": 1, "shape": 2}}
        assert set(snap["loop_bails"]) <= set(BAIL_CAUSES)


# -- scheduler bail matrix ----------------------------------------------------

class Doubler(Element):
    """Deterministic window-capable element: y = 2x, with injectable
    bail triggers. Mirrors exactly the surface the scheduler probes on
    tensor_filter (window_capable / swap_pending / process_window)."""

    ELEMENT_NAME = "test_doubler"
    CHAIN_FUSABLE = False      # keep a real worker thread + channel

    def __init__(self, name=None, *, sleep_s=0.02, fail_pts=(),
                 swap_bails=0, timer_after=None, **props):
        super().__init__(name, **props)
        self.calls = []                   # ("pf", pts) | ("win", [pts])
        self._sleep = sleep_s
        self._fail_pts = set(fail_pts)
        self._swap_bails = swap_bails
        self._timer_after = timer_after
        self._done = 0

    def negotiate(self, in_specs):
        return [in_specs[0]]

    def window_capable(self):
        return True

    def swap_pending(self):
        if self._swap_bails > 0:
            self._swap_bails -= 1
            return True
        return False

    def next_deadline(self):
        if self._timer_after is not None and \
                self._done >= self._timer_after:
            return time.perf_counter() + 60.0
        return None

    def _one(self, buf):
        out = TensorBuffer.of(np.asarray(buf.tensors[0]) * 2,
                              pts=buf.pts)
        return out

    def process(self, pad, buf):
        self.calls.append(("pf", buf.pts))
        if buf.pts in self._fail_pts:
            raise RuntimeError(f"boom at pts {buf.pts}")
        if self._sleep:
            time.sleep(self._sleep)
            self._sleep = 0.0             # only the head-start frame
        self._done += 1
        return [(0, self._one(buf))]

    def process_window(self, pad, bufs):
        pts = [b.pts for b in bufs]
        self.calls.append(("win", pts))
        if self._fail_pts.intersection(pts):
            raise RuntimeError(f"window boom at {pts}")
        self._done += len(bufs)
        return [(0, self._one(b)) for b in bufs]


def _run(frames, *, compiled=True, arm=2, window=4,
         expect_fail=False, **doubler_kw):
    """Push `frames` (np arrays, pts = index) + EOS, run to EOS, return
    (sink results, element, loop-stats dict)."""
    pipe = nns.Pipeline("cl_test")
    spec = TensorsSpec.of(TensorInfo(
        frames[0].shape, DType.from_name(frames[0].dtype.name)))
    src = AppSrc(spec=spec, name="src")
    dbl = Doubler(name="d", **doubler_kw)
    sink = TensorSink(name="out")
    for e in (src, dbl, sink):
        pipe.add(e)
    pipe.link(src, dbl)
    pipe.link(dbl, sink)
    for i, x in enumerate(frames):
        src.push(TensorBuffer.of(x, pts=i))
    src.end()                             # full trace queued before start
    r = nns.PipelineRunner(pipe, compiled_loop=compiled,
                           compiled_loop_arm=arm,
                           compiled_loop_window=window,
                           queue_capacity=max(16, len(frames) + 2))
    r.start()
    if expect_fail:
        with pytest.raises(StreamError):
            r.wait(60)
    else:
        r.wait(60)
    st = r.stats().get("d", {})
    loops = {k: st.get(k) for k in
             ("loop_entries", "compiled_steps", "loop_bails")}
    return sink.results, dbl, loops


def _frames(n, shape=(4, 2), dtype=np.float32, base=0):
    return [np.full(shape, base + i, dtype) for i in range(n)]


class TestBailMatrix:
    def test_steady_state_windows_with_exact_accounting(self):
        res, dbl, st = _run(_frames(10), arm=2, window=4)
        # trace: pts0 per-frame (streak 1), [1..4] and [5..8] windowed,
        # collection for pts9 hits EOS → per-frame 9, drain
        assert [b.pts for b in res] == list(range(10))
        assert st["loop_entries"] == 2
        assert st["compiled_steps"] == 8
        assert st["loop_bails"] == {"eos": 1}
        assert dbl.calls == [("pf", 0), ("win", [1, 2, 3, 4]),
                             ("win", [5, 6, 7, 8]), ("pf", 9)]

    def test_bit_parity_with_per_frame_mode(self):
        frames = _frames(12)
        res_on, _, st_on = _run(frames, compiled=True)
        res_off, _, st_off = _run(frames, compiled=False)
        assert st_on["compiled_steps"] > 0
        assert st_off["loop_entries"] is None     # loop never built
        assert len(res_on) == len(res_off) == 12
        for a, b in zip(res_on, res_off):
            assert a.pts == b.pts
            np.testing.assert_array_equal(np.asarray(a.tensors[0]),
                                          np.asarray(b.tensors[0]))

    def test_shape_change_bails_and_preserves_order(self):
        frames = _frames(5) + _frames(1, shape=(3, 3), base=50) \
            + _frames(4, base=100)
        res, dbl, st = _run(frames, arm=2, window=8)
        # the (3,3) frame at pts5 diverges mid-collection: parked, runs
        # per-frame AFTER the partial window, order preserved end-to-end
        assert st["loop_bails"].get("shape", 0) >= 1
        assert st["loop_entries"] >= 1
        assert [b.pts for b in res] == list(range(10))
        assert ("pf", 5) in dbl.calls         # divergent frame per-frame
        assert all(5 not in c[1] for c in dbl.calls if c[0] == "win")
        for b in res:                          # every value still 2x
            exp = np.asarray(frames[b.pts]) * 2
            np.testing.assert_array_equal(np.asarray(b.tensors[0]), exp)

    def test_window_error_reruns_per_frame_and_lands_exactly(self):
        # pts3 poisons both paths: the window [1..4] raises, every
        # frame re-runs per-frame, 1 and 2 still emit, the error policy
        # (fail-fast) fires on precisely pts3
        res, dbl, st = _run(_frames(10), arm=2, window=4,
                            fail_pts={3}, expect_fail=True)
        assert st["loop_bails"].get("error", 0) == 1
        assert st["loop_entries"] == 0         # the window never landed
        # the element's own log is the deterministic record: the window
        # raised, 1 and 2 re-ran (and emitted), 3 faulted per-frame —
        # nothing past the faulting frame ever ran
        assert dbl.calls == [("pf", 0), ("win", [1, 2, 3, 4]),
                             ("pf", 1), ("pf", 2), ("pf", 3)]
        # sink delivery during failure teardown is best-effort, but
        # whatever arrived is an in-order prefix of the pre-fault frames
        assert [b.pts for b in res] == list(range(len(res)))
        assert len(res) <= 3

    def test_window_only_error_recovers_completely(self):
        # poison pts -99 never matches a per-frame pts, but monkeypatch
        # the window to raise once: the re-run serves every frame
        class FlakyWindow(Doubler):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self._boomed = False

            def process_window(self, pad, bufs):
                if not self._boomed:
                    self._boomed = True
                    self.calls.append(("win", [b.pts for b in bufs]))
                    raise RuntimeError("transient window fault")
                return super().process_window(pad, bufs)

        pipe = nns.Pipeline("cl_flaky")
        frames = _frames(10)
        spec = TensorsSpec.of(TensorInfo(
            frames[0].shape, DType.from_name(frames[0].dtype.name)))
        src = AppSrc(spec=spec, name="src")
        dbl = FlakyWindow(name="d")
        sink = TensorSink(name="out")
        for e in (src, dbl, sink):
            pipe.add(e)
        pipe.link(src, dbl)
        pipe.link(dbl, sink)
        for i, x in enumerate(frames):
            src.push(TensorBuffer.of(x, pts=i))
        src.end()
        r = nns.PipelineRunner(pipe, compiled_loop=True,
                               compiled_loop_arm=2,
                               compiled_loop_window=4,
                               queue_capacity=16)
        r.start()
        r.wait(60)
        st = r.stats()["d"]
        assert st["loop_bails"].get("error", 0) == 1
        assert [b.pts for b in sink.results] == list(range(10))
        # the errored window's frames all re-ran per-frame, in order
        pf = [c[1] for c in dbl.calls if c[0] == "pf"]
        assert pf[:5] == [0, 1, 2, 3, 4]

    def test_window_build_failure_surfaces(self):
        """A window that cannot be BUILT is not an element error on a
        frame: no counted bail, no per-frame re-run carrying the run —
        the pipeline fails with the typed error."""
        class Unbuildable(Doubler):
            def process_window(self, pad, bufs):
                self.calls.append(("win", [b.pts for b in bufs]))
                raise WindowBuildError("scan does not compile here")

        pipe = nns.Pipeline("cl_unbuildable")
        frames = _frames(10)
        spec = TensorsSpec.of(TensorInfo(
            frames[0].shape, DType.from_name(frames[0].dtype.name)))
        src = AppSrc(spec=spec, name="src")
        dbl = Unbuildable(name="d")
        sink = TensorSink(name="out")
        for e in (src, dbl, sink):
            pipe.add(e)
        pipe.link(src, dbl)
        pipe.link(dbl, sink)
        for i, x in enumerate(frames):
            src.push(TensorBuffer.of(x, pts=i))
        src.end()
        r = nns.PipelineRunner(pipe, compiled_loop=True,
                               compiled_loop_arm=2,
                               compiled_loop_window=4,
                               queue_capacity=16)
        r.start()
        with pytest.raises(StreamError):
            r.wait(60)
        assert isinstance(r._error.__cause__ or r._error,
                          WindowBuildError)
        st = r.stats()["d"]
        assert st["loop_bails"].get("error", 0) == 0
        assert st["loop_entries"] == 0
        # nothing re-ran per-frame behind the failed window
        assert dbl.calls == [("pf", 0), ("win", [1, 2, 3, 4])]

    def test_swap_pending_is_a_transient_bail(self):
        res, dbl, st = _run(_frames(10), arm=2, window=4, swap_bails=1)
        # the first armed attempt bails (swap adoption happens
        # per-frame), the detector stays armed, the next frame windows
        assert st["loop_bails"].get("swap", 0) == 1
        assert st["loop_entries"] >= 1
        assert [b.pts for b in res] == list(range(10))

    def test_armed_timer_bails_to_per_frame(self):
        # after 3 frames the element holds a (future) deadline: every
        # armed attempt from then on bails — deadline-owning elements
        # must flush on time, which per-frame mode guarantees
        res, dbl, st = _run(_frames(10), arm=2, window=4, timer_after=3)
        assert st["loop_bails"].get("timer", 0) >= 1
        assert [b.pts for b in res] == list(range(10))
        assert all(len(c[1]) <= 4 for c in dbl.calls if c[0] == "win")

    def test_eos_drains_partial_window(self):
        # 4 frames, window 8: the one window collection runs into EOS,
        # pow2 round-down windows [1,2], the leftover (3) and the EOS
        # drain per-frame behind it
        res, dbl, st = _run(_frames(4), arm=2, window=8)
        assert st["loop_bails"] == {"eos": 1}
        assert st["loop_entries"] == 1
        assert st["compiled_steps"] == 2
        assert dbl.calls == [("pf", 0), ("win", [1, 2]), ("pf", 3)]
        assert [b.pts for b in res] == list(range(4))

    def test_pow2_round_down_leftover_stays_ordered(self):
        # 8 frames, window 8: pts0 per-frame, collection sweeps [1..7]
        # (7 frames) + EOS → k=4 window, leftover [5,6,7] per-frame
        res, dbl, st = _run(_frames(8), arm=2, window=8)
        assert st["compiled_steps"] == 4
        assert dbl.calls == [("pf", 0), ("win", [1, 2, 3, 4]),
                             ("pf", 5), ("pf", 6), ("pf", 7)]
        assert [b.pts for b in res] == list(range(8))


# -- real backend: lax.scan window vs per-frame invokes -----------------------

class TestBackendWindowParity:
    def test_invoke_window_bit_identical_to_per_frame(self):
        """The scan body IS the per-frame jitted fn — same weights,
        same frame order, byte-identical logits."""
        from nnstreamer_tpu.elements import TensorFilter

        filt = TensorFilter(
            name="f", compiled_loop=True,
            model="zoo://mobilenet_v2?width=0.35&input_size=32"
                  "&dtype=float32")
        spec = TensorsSpec.of(TensorInfo((1, 32, 32, 3), DType.FLOAT32))
        filt.negotiate([spec])
        filt.start()
        try:
            assert filt.window_capable()
            rng = np.random.default_rng(0)
            frames = [rng.normal(size=(1, 32, 32, 3)).astype(np.float32)
                      for _ in range(4)]
            bufs = [TensorBuffer.of(x, pts=i)
                    for i, x in enumerate(frames)]
            per = [filt.process(0, b)[0][1] for b in bufs]
            bufs2 = [TensorBuffer.of(x, pts=i)
                     for i, x in enumerate(frames)]
            win = [b for _, b in filt.process_window(0, bufs2)]
            assert len(win) == len(per) == 4
            for a, b in zip(per, win):
                assert a.pts == b.pts
                for ta, tb in zip(a.tensors, b.tensors):
                    np.testing.assert_array_equal(np.asarray(ta),
                                                  np.asarray(tb))
            be = filt.backend
            assert be.window_invokes >= 1
            assert be.window_frames >= 4
        finally:
            filt.stop()

    def test_window_that_cannot_compile_raises_build_error(
            self, monkeypatch):
        """The backend builds the K-frame scan ahead of running it and
        names a failure for what it is (the scheduler lets this type
        through instead of bailing to per-frame)."""
        import jax

        from nnstreamer_tpu.backends.xla import ModelBundle, XLABackend

        be = XLABackend()
        be.open({"model": ModelBundle(
            fn=lambda params, x: (x * 2.0,), params=None, name="m")})
        try:
            x = np.ones((2, 3), np.float32)

            def refuse(*a, **k):
                raise NotImplementedError("scan refused by the compiler")

            with monkeypatch.context() as mp:
                mp.setattr(jax.lax, "scan", refuse)
                with pytest.raises(WindowBuildError, match="2-frame"):
                    be.invoke_window([(x,), (x,)])
            assert be.window_invokes == 0
            # the failed bucket was not cached: a later build succeeds
            out = be.invoke_window([(x,), (x,)])
            assert np.asarray(out[1][0])[0, 0] == 2.0
        finally:
            be.close()
