"""The five readers of the program's own spans (source ``program_span``)
against hand-made spans with hand-computed values, and a tiny traced run
on the CPU that reports all five.  A CPU run gives counts and evidence
that the readers find their spans; its times are never device metrics."""

import json
import time
import types

import pytest

import tiny
from perfbench import harness, run
from perfbench.layer_metrics import (_spans, admit_starved_pct,
                                     dispatch_ms_per_step, emit_ms_per_step,
                                     readback_ms_per_step, step_host_ms)

FIVE = {"admit_starved_pct": admit_starved_pct,
        "step_host_ms": step_host_ms,
        "dispatch_ms_per_step": dispatch_ms_per_step,
        "readback_ms_per_step": readback_ms_per_step,
        "emit_ms_per_step": emit_ms_per_step}


def _ms(key, start_ms, dur_ms, t0=1000.0):
    return (key, t0 + start_ms * 1e-3, dur_ms * 1e-3)


# Two whole steps of element "llm"; a timer fire that did no work; a step
# of another element's timer that holds no backend span; a step that ends
# after the stretch; and children whose timer began before the stretch.
SPANS = [
    _ms("backend:llm:readback", -2, 1),             # orphan: no timer
    _ms("element:llm:emit", -1, 0.5),               # orphan
    # step A: 70 ms, waits 60
    _ms("element:llm:timer", 0, 70),
    _ms("llm:llm:admit_none_queued", 0.1, 0.1),
    _ms("backend:llm:prep", 1, 1),
    _ms("backend:llm:dispatch", 2, 2),
    _ms("backend:llm:invoke", 2, 64),
    _ms("backend:llm:wait", 4, 60),
    _ms("backend:llm:readback", 64, 2),
    _ms("llm:llm:sample", 66, 1),
    _ms("element:llm:emit", 67, 3),
    # an idle fire and another element's timer: no step
    _ms("element:llm:timer", 80, 0.2),
    _ms("element:batch:timer", 81, 5),
    # step B: 80 ms with a prefill; waits 10 + 55; nothing emitted
    _ms("element:llm:timer", 100, 80),
    _ms("llm:llm:admit", 100.1, 12.5),
    _ms("backend:llm:prep", 100.2, 0.5),
    _ms("backend:llm:dispatch", 100.7, 1.5),
    _ms("backend:llm:invoke", 100.7, 1.5),
    _ms("backend:llm:wait", 103, 10),
    _ms("backend:llm:prep", 114, 1),
    _ms("backend:llm:dispatch", 115, 2),
    _ms("backend:llm:invoke", 115, 58),
    _ms("backend:llm:wait", 117, 55),
    _ms("backend:llm:readback", 172, 1),
    # step C begins inside the stretch and ends after it: left out
    _ms("element:llm:timer", 450, 70),
    _ms("llm:llm:admit_blocked", 450.1, 0.1),
    _ms("backend:llm:prep", 451, 40),
    _ms("backend:llm:dispatch", 491, 5),
    _ms("llm:llm:admit_full", 600, 0.1),
]
WINDOW = types.SimpleNamespace(end=1000.0 + 0.5)
CTX = {"host_spans": SPANS, "trace_window": WINDOW}


def test_split_keeps_colons_in_the_element_name():
    assert _spans.split("backend:w0/llm:1:wait") == ("backend", "w0/llm:1",
                                                     "wait")


def test_steps_are_timers_that_launched_device_work():
    found = sorted(_spans.steps(CTX), key=lambda s: s["timer"])
    assert [round(1e3 * s["timer"]) for s in found] == [70, 80]
    assert found[0]["emit"] == pytest.approx(0.003)
    assert "emit" not in found[1]
    assert found[1]["wait"] == pytest.approx(0.065)
    # without the stretch's end every timer that holds a dispatch counts
    assert len(_spans.steps({"host_spans": SPANS})) == 3


@pytest.mark.parametrize("base,value", [
    ("admit_starved_pct", 25.0),                    # 1 of 4 admissions
    ("step_host_ms", (70 - 60 + 80 - 65) / 2),      # 12.5
    ("dispatch_ms_per_step", (1 + 2 + 0.5 + 1.5 + 1 + 2) / 2),   # 4.0
    ("readback_ms_per_step", (2 + 1) / 2),          # 1.5
    ("emit_ms_per_step", 3 / 2),                    # 1.5
])
def test_reader_against_hand_computed_value(base, value):
    assert FIVE[base].read(CTX) == pytest.approx(value)


def test_nothing_starved_nothing_emitted_reads_zero_not_nothing():
    spans = [s for s in SPANS
             if s[0] not in ("llm:llm:admit_none_queued", "element:llm:emit",
                             "backend:llm:readback")]
    ctx = {"host_spans": spans, "trace_window": WINDOW}
    assert admit_starved_pct.read(ctx) == 0.0
    assert emit_ms_per_step.read(ctx) == 0.0
    assert readback_ms_per_step.read(ctx) == 0.0


@pytest.mark.parametrize("base", sorted(FIVE))
def test_a_program_without_the_child_spans_has_no_step(base):
    """The parent commit records timer and invoke spans only."""
    spans = [s for s in SPANS if s[0].rsplit(":", 1)[1]
             in ("timer", "invoke", "process")]
    assert FIVE[base].read({"host_spans": spans}) is None
    assert FIVE[base].read({"host_spans": []}) is None


def test_manifest_lists_the_five_beside_the_nine():
    m = harness.load_manifest()
    new = [e for e in m["per_layer"] if e["source"] == "program_span"]
    assert {e["name"] for e in new} == {b + ".tokens" for b in FIVE}
    assert m["per_layer"][-5:] == new and len(m["per_layer"]) == 14
    for e in new:
        assert e["moves"] == "tokens_per_s"
        assert e["workloads"] == ["ouro_chat_backlog"]


def test_tiny_traced_run_reports_all_five():
    import jax

    cell = tiny.cell()
    cell.per_layer = [{"name": b + ".tokens", "unit": "x",
                       "moves": "tokens_per_s"} for b in FIVE]
    devs = jax.devices()[:1]
    out = json.loads(run.run_cell(
        cell, 2**31 + 29, 1.5, True, devs, harness.device_info(devs),
        time.perf_counter()))
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == {b + ".tokens" for b in FIVE}
    assert 0.0 <= got["admit_starved_pct.tokens"] <= 100.0
    assert got["dispatch_ms_per_step.tokens"] > 0
    assert got["readback_ms_per_step.tokens"] > 0
    assert got["emit_ms_per_step.tokens"] > 0      # every step emits tokens
    assert got["step_host_ms.tokens"] > (
        got["dispatch_ms_per_step.tokens"]
        + got["readback_ms_per_step.tokens"]
        + got["emit_ms_per_step.tokens"])
    assert out["device"]["platform"] == "cpu"      # never a device metric
