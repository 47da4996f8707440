"""Runtime scheduler + end-to-end slice tests with the fake (custom)
backend — the XLA-free backbone of element testing (SURVEY.md §4
takeaway a: custom-easy functions as fake frameworks)."""

import time

import numpy as np
import pytest

from nnstreamer_tpu import (
    TensorBuffer,
    TensorsSpec,
    parse_launch,
    register_custom_easy,
    run_pipeline,
)
from nnstreamer_tpu.backends.custom import unregister_custom_easy
from nnstreamer_tpu.core.errors import StreamError
from nnstreamer_tpu.elements.sources import AppSrc
from nnstreamer_tpu.runtime.scheduler import PipelineRunner


@pytest.fixture(autouse=True)
def _clean_models():
    names = []

    def reg(name, *a, **kw):
        names.append(name)
        return register_custom_easy(name, *a, **kw)

    yield reg
    for n in names:
        unregister_custom_easy(n)


class TestEndToEnd:
    def test_video_to_sink(self):
        p = parse_launch(
            "videotestsrc width=8 height=8 num-buffers=5 ! tensor_converter "
            "! tensor_sink name=out"
        )
        run_pipeline(p, timeout=10)
        sink = p.get("out")
        assert len(sink.results) == 5
        assert sink.results[0].tensors[0].shape == (1, 8, 8, 3)
        assert sink.eos.is_set()

    def test_full_slice_with_fake_filter(self, _clean_models):
        # converter → transform → filter(custom) → sink : the M4 slice
        _clean_models("double", lambda ts: tuple(2 * t for t in ts))
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=3 pattern=solid "
            "solid-color=10 ! tensor_converter ! "
            "tensor_transform mode=typecast option=float32 ! "
            "tensor_filter framework=custom model=double ! tensor_sink name=out"
        )
        run_pipeline(p, timeout=10)
        out = p.get("out").results
        assert len(out) == 3
        np.testing.assert_array_equal(
            out[0].tensors[0], np.full((1, 4, 4, 3), 20.0, np.float32)
        )

    def test_fusion_rewrites_graph_same_result(self, _clean_models):
        _clean_models("plus1", lambda ts: tuple(t + 1 for t in ts))
        desc = (
            "videotestsrc width=4 height=4 num-buffers=2 pattern=solid "
            "solid-color=5 ! tensor_converter ! "
            "tensor_transform mode=typecast option=float32 ! "
            "tensor_transform mode=arithmetic option=mul:2.0 ! "
            "tensor_filter framework=custom model=plus1 ! tensor_sink name=out"
        )
        p_fused = parse_launch(desc)
        run_pipeline(p_fused, timeout=10, optimize=True)
        p_plain = parse_launch(desc)
        run_pipeline(p_plain, timeout=10, optimize=False)
        # fusion removed the transforms from the graph
        assert not any(
            e.ELEMENT_NAME == "tensor_transform" for e in p_fused.elements.values()
        )
        a = p_fused.get("out").results[0].tensors[0]
        b = p_plain.get("out").results[0].tensors[0]
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, np.full((1, 4, 4, 3), 11.0, np.float32))

    def test_appsrc_push(self):
        p = parse_launch("appsrc dims=2:3 types=float32 name=in ! tensor_sink name=out")
        runner = PipelineRunner(p).start()
        src: AppSrc = p.get("in")
        for i in range(4):
            src.push(np.full((3, 2), i, np.float32))
        src.end()
        runner.wait(10)
        assert len(p.get("out").results) == 4

    def test_frames_per_tensor_batching(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=6 ! "
            "tensor_converter frames-per-tensor=3 ! tensor_sink name=out"
        )
        run_pipeline(p, timeout=10)
        out = p.get("out").results
        assert len(out) == 2
        assert out[0].tensors[0].shape == (3, 4, 4, 3)

    def test_error_propagates(self, _clean_models):
        def boom(ts):
            raise RuntimeError("backend exploded")

        # declare passthrough spec so negotiation's zero-probe is skipped
        # and the failure happens in the streaming hot loop
        _clean_models("boom", boom, infer_out=lambda s: s)
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=2 ! tensor_converter ! "
            "tensor_filter framework=custom model=boom ! tensor_sink name=out"
        )
        with pytest.raises(StreamError, match="backend exploded"):
            run_pipeline(p, timeout=10)

    def test_filter_stats(self, _clean_models):
        _clean_models("idle", lambda ts: ts)
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=5 ! tensor_converter ! "
            "tensor_filter framework=custom model=idle name=f ! tensor_sink name=out"
        )
        run_pipeline(p, timeout=10)
        f = p.get("f")
        assert f._invoke_count == 5
        assert f.latency_us >= 0
        assert f.throughput > 0


class TestDecoderSlice:
    def test_image_labeling(self, _clean_models, tmp_path):
        labels = tmp_path / "labels.txt"
        labels.write_text("cat\ndog\nbird\n")

        def classifier(ts):
            scores = np.zeros((1, 3), np.float32)
            scores[0, 1] = 0.9
            return (scores,)

        _clean_models(
            "clf", classifier,
            out_spec=TensorsSpec.from_strings("3:1", "float32"),
        )
        p = parse_launch(
            f"videotestsrc width=4 height=4 num-buffers=2 ! tensor_converter ! "
            f"tensor_filter framework=custom model=clf ! "
            f"tensor_decoder mode=image_labeling option1={labels} ! "
            f"tensor_sink name=out"
        )
        run_pipeline(p, timeout=10)
        res = p.get("out").results
        assert res[0].meta["label"] == "dog"
        assert bytes(res[0].tensors[0].tobytes()) == b"dog"

    def test_missing_labels_file(self):
        from nnstreamer_tpu.core.errors import PipelineError

        with pytest.raises(PipelineError, match="not found"):
            parse_launch(
                "appsrc dims=3:1 ! tensor_decoder mode=image_labeling "
                "option1=/nonexistent/labels.txt ! tensor_sink"
            )


class TestBackpressure:
    def test_slow_sink_does_not_deadlock(self):
        p = parse_launch(
            "videotestsrc width=4 height=4 num-buffers=20 ! tensor_converter "
            "! tensor_sink name=out"
        )
        sink = p.get("out")
        orig = sink.render

        def slow_render(buf):
            time.sleep(0.005)
            orig(buf)

        sink.render = slow_render
        run_pipeline(p, timeout=30)
        assert len(sink.results) == 20


class TestReviewRegressions:
    def test_stop_unblocks_appsrc(self):
        p = parse_launch("appsrc dims=2:2 name=in ! tensor_sink name=out")
        runner = PipelineRunner(p).start()
        p.get("in").push(np.zeros((2, 2), np.float32))
        time.sleep(0.05)
        runner.stop()
        runner.wait(5)  # must not hang

    def test_arith_int_preserves_dtype(self):
        from nnstreamer_tpu.elements.transform import TransformProgram

        prog = TransformProgram("arithmetic", "add:2")
        out = prog.apply(np, np.array([1, 2], np.uint8))
        assert out.dtype == np.uint8
        info = prog.out_info(
            __import__("nnstreamer_tpu").TensorInfo((2,), "uint8"))
        assert info.dtype.type_name == "uint8"

    def test_arith_promoting_matches_spec(self):
        from nnstreamer_tpu.elements.transform import TransformProgram

        prog = TransformProgram("arithmetic", "add:-127.5,div:127.5")
        x = np.array([0, 255], np.uint8)
        out = prog.apply(np, x)
        assert out.dtype == np.float32  # matches declared transfer exactly
        info = prog.out_info(
            __import__("nnstreamer_tpu").TensorInfo((2,), "uint8"))
        assert info.dtype.type_name == "float32"

    def test_audio_adapter(self):

        from nnstreamer_tpu.graph.media import AudioSpec

        spec = AudioSpec(sample_rate=8000, channels=2, sample_format="S16LE")
        p = parse_launch(
            "appsrc name=in ! tensor_converter frames-per-tensor=160 "
            "! tensor_sink name=out")
        p.get("in").set_props(spec=spec)
        runner = PipelineRunner(p).start()
        src = p.get("in")
        for _ in range(4):  # 4 x 100 samples -> 2 x 160 with 80 left over
            src.push(TensorBuffer.of(np.zeros((100, 2), np.int16)))
        src.end()
        runner.wait(10)
        out = p.get("out").results
        assert len(out) == 2
        assert out[0].tensors[0].shape == (160, 2)

    def test_zoo_unknown_model_actionable(self):
        from nnstreamer_tpu.core.errors import NegotiationError

        p = parse_launch(
            "videotestsrc num-buffers=1 ! tensor_converter ! "
            "tensor_filter framework=xla model=zoo://nope ! tensor_sink")
        with pytest.raises(NegotiationError, match="no zoo model"):
            p.negotiate()

    def test_prop_after_ref_rejected(self):
        from nnstreamer_tpu.core.errors import PipelineError

        with pytest.raises(PipelineError, match="pad reference"):
            parse_launch("appsrc dims=2 ! m. foo=1 tensor_sink name=m")


class TestInputPipeline:
    """Double-buffered H2D staging (runtime/input_pipeline.py)."""

    def test_prefetch_yields_all_in_order(self):
        import jax

        from nnstreamer_tpu.runtime import prefetch_to_device

        batches = [np.full((4,), i, np.float32) for i in range(7)]
        out = list(prefetch_to_device(iter(batches), depth=2))
        assert len(out) == 7
        for i, y in enumerate(out):
            assert isinstance(y, jax.Array)
            np.testing.assert_array_equal(np.asarray(y), batches[i])

    def test_prefetch_overlaps_staging(self):
        """The producer runs ahead of the consumer (double buffering):
        with depth=2 the 2nd batch is staged while the 1st is consumed."""
        import threading
        import time

        from nnstreamer_tpu.runtime import prefetch_to_device

        staged = []
        gate = threading.Event()

        def slow_source():
            for i in range(4):
                staged.append(i)
                yield np.full((2,), i, np.float32)
            gate.set()

        it = prefetch_to_device(slow_source(), depth=2)
        first = next(it)
        time.sleep(0.05)            # let the worker run ahead
        assert len(staged) >= 2     # staged beyond what was consumed
        rest = list(it)
        assert len(rest) == 3 and gate.is_set()
        np.testing.assert_array_equal(np.asarray(first), [0, 0])

    def test_prefetch_propagates_source_error(self):
        from nnstreamer_tpu.runtime import prefetch_to_device

        def bad():
            yield np.zeros(2, np.float32)
            raise ValueError("sensor unplugged")

        it = prefetch_to_device(bad(), depth=1)
        next(it)
        with pytest.raises(ValueError, match="sensor unplugged"):
            for _ in it:
                pass

    def test_feeder_push_pull_and_close(self):
        from nnstreamer_tpu.runtime import DeviceFeeder

        f = DeviceFeeder(depth=2)
        f.put(np.arange(3, dtype=np.float32))
        f.put(np.arange(3, dtype=np.float32) * 2)
        f.close()
        a = f.get()
        b = f.get()
        np.testing.assert_array_equal(np.asarray(b), [0.0, 2.0, 4.0])
        assert f.get() is None
        with pytest.raises(RuntimeError, match="closed"):
            f.put(np.zeros(1, np.float32))

    def test_feeder_rejects_bad_depth(self):
        from nnstreamer_tpu.runtime import DeviceFeeder, prefetch_to_device

        with pytest.raises(ValueError, match="depth"):
            DeviceFeeder(depth=0)
        with pytest.raises(ValueError, match="depth"):
            list(prefetch_to_device(iter([]), depth=0))


def test_stats_report_queue_wait():
    """Per-element queue-wait counters (GstShark interlatency analog)
    separate starvation from slow elements in stats()."""
    import nnstreamer_tpu as nns
    from nnstreamer_tpu.tensor.buffer import TensorBuffer

    pipe = nns.parse_launch(
        "appsrc name=src dims=4:1 types=float32 ! "
        "tensor_transform mode=arithmetic option=add:1.0 name=tr ! "
        "tensor_sink name=out")
    runner = nns.PipelineRunner(pipe, optimize=False).start()
    for i in range(6):
        pipe.get("src").push(TensorBuffer.of(
            np.ones((1, 4), np.float32), pts=i))
    pipe.get("src").end()
    runner.wait(30)
    runner.stop()
    st = runner.stats()
    tr = st["tr"]
    assert tr["buffers"] == 6
    assert "queue_wait_avg_us" in tr and "queue_wait_max_us" in tr
    assert tr["queue_wait_max_us"] >= tr["queue_wait_avg_us"] >= 0.0
    assert tr["proctime_avg_us"] > 0.0


def _late_timer(fires: int):
    from nnstreamer_tpu.graph.pipeline import Element

    class LateTimer(Element):
        """A serving element whose step outlasts its own window: every
        deadline it arms is already past when the scheduler reads it."""

        ELEMENT_NAME = "test_late_timer"
        CHAIN_FUSABLE = False

        def __init__(self, name=None, **props):
            super().__init__(name, **props)
            self.seen = 0
            self.seen_at_fire = []
            self._deadline = None

        def negotiate(self, in_specs):
            return [in_specs[0]]

        def process(self, pad, buf):
            self.seen += 1
            if self._deadline is None and len(self.seen_at_fire) < fires:
                self._deadline = time.perf_counter() - 1.0
            return []

        def next_deadline(self):
            return self._deadline

        def on_timer(self):
            self.seen_at_fire.append(self.seen)
            time.sleep(0.005)             # the producer refills the queue
            self._deadline = (time.perf_counter() - 1.0
                              if len(self.seen_at_fire) < fires else None)
            return []

    return LateTimer(name="late")


def test_a_timer_already_due_waits_for_what_is_queued_and_no_more():
    """An element whose every deadline is past (`tensor_llm` at many
    rows: a step's emissions outlast `admit_window_ms`) still has its
    input read: what the channel held when the timer was found due,
    and no more than that, so the timer is not held back either."""
    import nnstreamer_tpu as nns
    from nnstreamer_tpu.elements.sinks import TensorSink
    from nnstreamer_tpu.tensor.dtypes import DType
    from nnstreamer_tpu.tensor.info import TensorInfo

    cap, n, fires = 4, 14, 3
    pipe = nns.Pipeline("late_timer")
    src = AppSrc(spec=TensorsSpec.of(TensorInfo((1, 4), DType.FLOAT32)),
                 name="src")
    late = _late_timer(fires)
    sink = TensorSink(name="out")
    for e in (src, late, sink):
        pipe.add(e)
    pipe.link(src, late)
    pipe.link(late, sink)
    for i in range(n):
        src.push(TensorBuffer.of(np.ones((1, 4), np.float32), pts=i))
    src.end()
    r = nns.PipelineRunner(pipe, queue_capacity=cap)
    r.start()
    r.wait(30)
    r.stop()
    at = late.seen_at_fire
    assert late.seen == n and len(at) == fires
    # the input was read between the fires (it was not before: every
    # fire saw the one message that armed the first deadline)
    assert all(b > a for a, b in zip(at, at[1:]))
    # and a fire waits for no more than the channel holds
    assert all(b - a <= cap for a, b in zip(at, at[1:]))
    assert at[0] <= 1 + cap
    assert r.stats()["late"]["timer_fires"] == fires
