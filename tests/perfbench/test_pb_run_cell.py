"""A whole run at a tiny size on the CPU, past the look for a chip: the
same runner, generator, checks and result line as on the chip.  Then the
same run with the timed path broken underneath, which has to come out as
not correct, and the lower-precision control at a size a test can hold.

These runs print counts and correctness only; a rate or a time read here
is never a device metric.
"""

import collections
import json
import time

import pytest

import tiny
from perfbench import harness, run
from perfbench.runners import llm

SEED = 2**31 + 11


def _run(seconds=1.5, trace=False, cell=None):
    import jax

    devs = jax.devices()[:1]
    line = run.run_cell(cell or tiny.cell(), SEED, seconds, trace, devs,
                        harness.device_info(devs), time.perf_counter())
    return json.loads(line)


def test_sound_run_is_correct(capsys):
    out = _run()
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu"     # never a device metric
    printed = capsys.readouterr().out
    for name in ("requests_passed_over", "requests_in_flight",
                 "answers_of_wrong_length", "pipeline_error",
                 "compiles_in_window", "served_token_gap_max"):
        assert f"check {name}" in printed         # beside its limit
    assert "setup_phases" in printed


def test_altered_token_is_not_correct(monkeypatch, capsys):
    """A token altered where it is produced: one in seven sampled tokens
    is off by one."""
    from nnstreamer_tpu.llm import engine

    sound = engine.LLMEngine._sample
    count = [0]

    def broken(self, req, logits):
        count[0] += 1
        tok = sound(self, req, logits)
        return (tok + 1) % logits.shape[0] if count[0] % 7 == 0 else tok

    monkeypatch.setattr(engine.LLMEngine, "_sample", broken)
    out = _run()
    assert out["correct"] is False
    assert "served_token_gap_max" in capsys.readouterr().out


def test_short_answer_is_not_correct(monkeypatch):
    """A request answered with fewer tokens than it asked for."""
    from nnstreamer_tpu.llm import engine

    sound = engine.LLMEngine.submit

    def broken(self, prompt, **kw):
        if kw.get("req_id", "").startswith("r"):
            kw["max_new_tokens"] = max(1, kw["max_new_tokens"] - 1)
        return sound(self, prompt, **kw)

    monkeypatch.setattr(engine.LLMEngine, "submit", broken)
    out = _run()
    assert out["correct"] is False and out["failed"] > 0


def test_starved_long_requests_are_not_correct(monkeypatch, capsys):
    """Shortest prompt first: the long prompts wait for ever under a
    standing backlog, and more tokens come out for it."""
    from nnstreamer_tpu.llm import engine

    sound = engine.LLMEngine._admit

    def broken(self, pending):
        self.queue = collections.deque(
            sorted(self.queue, key=lambda r: int(r.prompt.shape[0])))
        return sound(self, pending)

    monkeypatch.setattr(engine.LLMEngine, "_admit", broken)
    out = _run()
    assert out["correct"] is False and out["failed"] > 0
    assert "check requests_passed_over" in capsys.readouterr().out


def _req(rid, out_len, times, done):
    r = llm.Request(rid, None, out_len)
    r.times, r.tokens, r.done = list(times), [0] * len(times), done
    return r


JUDGE_CASES = {
    # name: (requests in the order submitted, then what _judge counts)
    "sound": ([_req("a", 2, [1, 2], True), _req("b", 3, [1, 2], False),
               _req("c", 2, [3, 4], True), _req("d", 2, [], False)],
              {"judged": 3, "good": 2, "wrong": 0, "passed_over": 0,
               "in_flight": 1}),
    "passed_over": ([_req("a", 2, [1, 2], True), _req("b", 3, [], False),
                     _req("c", 2, [3, 4], True)],
                    {"judged": 3, "good": 2, "wrong": 0, "passed_over": 1,
                     "in_flight": 0}),
    "started_after_the_window": (
        [_req("a", 2, [1, 2], True), _req("b", 2, [11, 12], True),
         _req("c", 2, [3, 4], True)],
        {"judged": 3, "good": 2, "wrong": 0, "passed_over": 1,
         "in_flight": 0}),
    "wrong_length": ([_req("a", 3, [1, 2], True), _req("b", 1, [1, 2], False),
                      _req("c", 2, [3, 4], True)],
                     {"judged": 3, "good": 1, "wrong": 2, "passed_over": 0,
                      "in_flight": 1}),
    "finished_after_the_window": (
        [_req("a", 2, [1, 2], True), _req("b", 2, [9, 12], True)],
        {"judged": 1, "good": 1, "wrong": 0, "passed_over": 0,
         "in_flight": 1}),
    "nothing_finished": ([_req("a", 2, [1], False)],
                         {"judged": 0, "good": 0, "wrong": 0,
                          "passed_over": 0, "in_flight": 1}),
}


@pytest.mark.parametrize("case", sorted(JUDGE_CASES))
def test_backlog_is_judged_in_the_order_submitted(case):
    reqs, want = JUDGE_CASES[case]
    got = llm.Runner._judge(reqs, 10.0)
    assert {k: len(v) for k, v in got.items()} == want


def test_control_reads_wider_than_the_sound_run():
    """The control at a test's size: the int8 reference's first choices
    lie further below the reference's best than the served tokens do."""
    import jax

    devs = jax.devices()[:1]
    cell = tiny.cell()
    cell.config["check"]["sample_requests"] = 1000      # every request
    r = llm.Runner(cell, SEED, 1.5, False, devs)
    phases = harness.Phases(time.perf_counter())
    try:
        r.setup(phases)
        obs = r.window(phases)
    finally:
        r.teardown()
    got = r.control_readings(obs, ["int8"])
    assert got["tokens"] > 0
    assert set(got["sound"]) == set(got["int8"]) == {
        "served_token_gap_max", "served_token_gap_mean",
        "served_token_off_best_pct"}
    assert got["sound"]["served_token_gap_max"] < 1e-4
    assert got["int8"]["served_token_gap_max"] > 1e-4
    assert got["int8"]["served_token_gap_mean"] > \
        got["sound"]["served_token_gap_mean"]


def test_every_limit_in_the_configuration_is_a_check(capsys):
    cell = tiny.cell()
    cell.config["check"]["limits"] = {"served_token_gap_max": 1e-4,
                                      "served_token_gap_mean": 0.0,
                                      "served_token_off_best_pct": 50.0}
    _run(cell=cell)
    printed = capsys.readouterr().out
    for name in cell.config["check"]["limits"]:
        assert f"check {name}" in printed


def test_trace_run_reports_layer_metrics_through_their_readers():
    """`--trace 1` on the CPU: the readers of program counters and the
    generator's clock report; the device readers find a trace without a
    TPU plane and report nothing."""
    cell = tiny.cell()
    names = ["gen_lag_p99_ms.tokens", "compiles_in_window.tokens",
             "decode_batch_avg.tokens", "kv_live_gb.tokens",
             "admission_blocked.tokens", "answer_stall_max_ms.tokens",
             "decode_step_roofline.tokens",
             "device_idle_pct.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    out = _run(trace=True, cell=cell)
    got = out["metrics"]
    assert got["compiles_in_window.tokens"]["value"] == 0
    assert 1.0 <= got["decode_batch_avg.tokens"]["value"] <= 4.5
    assert got["kv_live_gb.tokens"]["value"] > 0
    assert "gen_lag_p99_ms.tokens" in got
    assert 0 < got["answer_stall_max_ms.tokens"]["value"] < 1500
    assert "decode_step_roofline.tokens" not in got
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_stall_watch_says_where_the_threads_stand(capfd):
    last = [time.perf_counter()]
    with harness.StallWatch(lambda: last[0], limit_s=0.3):
        time.sleep(0.25)
        last[0] = time.perf_counter()           # an answer: no stall yet
        time.sleep(0.25)
        assert "stall" not in capfd.readouterr().err
        time.sleep(0.6)
    err = capfd.readouterr().err
    assert err.count("nothing answered for") == 1   # said once a stall
    assert "test_stall_watch_says_where_the_threads_stand" in err
