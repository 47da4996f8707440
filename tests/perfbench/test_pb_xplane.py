"""The reduction from a profiler trace to metrics, on a small recorded
trace (five runs of one jitted matmul on a TPU v5 lite, my chip run,
PR 24) and on synthetic spans."""

import os

import pytest

from perfbench import harness, stats, xplane

RECORDED = os.path.join(harness.ROOT, "perfbench", "data",
                        "recorded_step.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.read_xplane(RECORDED)


def test_recorded_trace_planes(recorded):
    assert list(recorded.modules) == ["/device:TPU:0"]
    runs = recorded.modules["/device:TPU:0"]
    assert len(runs) == 5
    assert {xplane.module_base(n) for n, _, _ in runs} == {
        "jit_my_named_step"}
    assert all(91e-6 < d < 92e-6 for _, _, d in runs)
    assert len(recorded.ops["/device:TPU:0"]) == 15
    assert any(n == "perfbench_marker" for n, _, _ in recorded.host)


def test_recorded_trace_reduction(recorded):
    runs = recorded.modules["/device:TPU:0"]
    start, end = runs[0][1] - 1e-3, runs[-1][1] + 1e-3
    n, total = xplane.module_time(recorded, "jit_my_named", start, end)
    assert n == 5 and total == pytest.approx(sum(d for _, _, d in runs))
    busy = xplane.busy_seconds(recorded, start, end)
    assert busy == pytest.approx(total, rel=0.01)
    gaps = xplane.idle_gaps(recorded, start, end)
    assert sum(b - a for a, b in gaps) == pytest.approx(end - start - busy)
    top = xplane.top_device_ops(recorded, start, end)
    assert top[0][0] == "program:jit_my_named_step"
    assert top[1][0] == "op:convolution_tanh_fusion"
    # a trace round-trips through JSON
    again = xplane.Trace.from_json(recorded.to_json())
    assert xplane.busy_seconds(again, start, end) == busy


def test_names():
    assert xplane.module_base("jit_paged_decode_step(123)") == \
        "jit_paged_decode_step"
    assert xplane.op_base("%fusion.2169 = s32[8]{0} fusion(s32[8] %p)") == \
        "fusion"
    assert xplane.op_base("%copy-start = (bf16[2]) copy-start(%x)") == \
        "copy-start"


def test_union_and_gaps():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union_length(spans) == 3.0
    assert stats.gaps(spans, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                            (4.0, 5.0)]
    assert stats.gaps([], 0.0, 1.0) == [(0.0, 1.0)]
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 99) == 99


def test_idle_gaps_go_to_the_innermost_host_span():
    tr = xplane.Trace(
        modules={"d": [("jit_f(1)", 1.0, 1.0), ("jit_f(1)", 3.0, 1.0)]},
        ops={"d": [("%a = x", 1.0, 1.0), ("%b = y", 3.0, 1.0)]},
        host=[(xplane.MARKER, 0.5, 0.0)])
    assert xplane.marker_time(tr) == 0.5
    assert xplane.busy_seconds(tr, 0.0, 5.0) == 2.0
    gaps = xplane.idle_gaps(tr, 0.0, 5.0)
    assert gaps == [(0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]
    host = [("element:f:process", 0.0, 5.0), ("backend:f:invoke", 2.2, 0.6)]
    by = dict(map(tuple, xplane.attribute_gaps(gaps, host)))
    assert by == {"element:f:process": 2.0, "backend:f:invoke": 1.0}
    assert xplane.attribute_gaps([(9.0, 10.0)], host) == [
        ["nothing_recorded", 1.0]]
    # a program run that straddles the window's edge is not counted
    assert xplane.module_time(tr, "jit_f", 1.5, 5.0) == (1, 1.0)


def test_gap_attribution_sweep_agrees_with_the_plain_search():
    """The one-pass sweep gives each gap to the same span as a search
    of every span for every gap, on seeded overlapping spans."""
    import random

    rng = random.Random(7)
    host = [(f"s{i % 5}", rng.uniform(0, 10), rng.uniform(0.01, 3))
            for i in range(60)]
    gaps = [(g, g + rng.uniform(0.001, 0.2))
            for g in (rng.uniform(-1, 12) for _ in range(200))]
    plain = {}
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        cover = [(d, s, n) for n, s, d in sorted(host, key=lambda x: x[1])
                 if s <= mid <= s + d]
        key = min(cover, key=lambda c: c[0])[2] if cover else "nothing_recorded"
        plain[key] = plain.get(key, 0.0) + (g1 - g0)
    swept = dict(map(tuple, xplane.attribute_gaps(gaps, host, limit=99)))
    assert swept.keys() == plain.keys()
    for k in plain:
        assert abs(swept[k] - plain[k]) < 1e-9
