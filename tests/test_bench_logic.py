"""Unit tests for bench.py's pure decision logic.

The bench mostly measures (driver-run on the real chip), but its
operating-point selection and stats helpers are plain functions whose
regressions would silently misreport results — pin them here (no jax,
no chip)."""

import bench


def test_percentile_bounds_and_interpolation():
    assert bench._percentile([], 50) == 0.0
    assert bench._percentile([7.0], 99) == 7.0
    vals = sorted([1.0, 2.0, 3.0, 4.0])
    assert bench._percentile(vals, 0) == 1.0
    assert bench._percentile(vals, 100) == 4.0
    assert bench._percentile(vals, 50) in (2.0, 3.0)


def _pt(fps, p50):
    return {"fps": fps, "p50_ms": p50}


def test_offload_chooser_prefers_target_box():
    # points meeting fps>=200 and p50<=60 win on lowest p50
    curve = {"0.0": _pt(210.0, 55.0), "3.0": _pt(250.0, 58.0),
             "8.0": _pt(300.0, 70.0)}
    out = bench._assemble_offload(curve)
    assert out["chosen_delay_ms"] == 0.0
    assert out["sweep"] is curve


def test_offload_chooser_near_best_fps_takes_lower_p50():
    # nothing in the target box: within 5% of best fps, lowest p50 wins
    # (trial-4 regression: 283 FPS @ 96ms must beat 285 FPS @ 112ms)
    curve = {"3.0": _pt(283.0, 96.1), "8.0": _pt(284.8, 111.7),
             "32.0": _pt(152.7, 129.7)}
    out = bench._assemble_offload(curve)
    assert out["chosen_delay_ms"] == 3.0


def test_offload_chooser_sub60_pool_preferred():
    # a sub-60ms point exists: the pool narrows to it even at lower fps
    curve = {"0.0": _pt(120.0, 45.0), "8.0": _pt(280.0, 100.0)}
    out = bench._assemble_offload(curve)
    assert out["chosen_delay_ms"] == 0.0


def test_offload_chooser_survives_errors_and_empty():
    curve = {"0.0": {"error": "boom"}, "8.0": _pt(100.0, 90.0)}
    out = bench._assemble_offload(curve)
    assert out["chosen_delay_ms"] == 8.0
    all_bad = {"0.0": {"error": "a"}, "8.0": {"error": "b"}}
    assert bench._assemble_offload(all_bad) == {"sweep": all_bad}


def test_family_registry_covers_main_order():
    ordered = bench._ordered_families()
    assert set(ordered) == set(bench._FAMILIES)
    assert len(ordered) == len(bench._FAMILIES)
    # the headline config must run first: a kill minutes in still ships
    # the driver's headline metric
    assert ordered[0] == "cfg_label_device"


def test_offload_median_spread():
    runs = [_pt(100.0, 50.0), _pt(300.0, 40.0), _pt(200.0, 45.0)]
    med = bench._offload_median(runs)
    assert med["fps"] == 200.0
    assert med["runs"] == 3
    assert med["fps_spread"] == [100.0, 300.0]
    assert med["p50_spread_ms"] == [40.0, 50.0]
    assert bench._offload_median([]) == {}
    assert bench._offload_median([{}, {"error": "x"}]) == {}
    # even count (budget-truncated 2-run point): lower-middle, never
    # the best run of a 3x-variance metric
    two = bench._offload_median([_pt(285.0, 100.0), _pt(86.0, 90.0)])
    assert two["fps"] == 86.0
    assert two["fps_spread"] == [86.0, 285.0]


# -- kill-resilience contract (round-5 VERDICT #1/#6) ------------------------
# The bench must ship data no matter when the driver kills it. These
# drive the REAL orchestration loop (subprocess families, budgets,
# timeouts, snapshot-per-family) with fake measurement families
# (BENCH_SELFTEST=fake — no jax, no chip), in miliseconds not minutes.

import json
import os
import signal
import subprocess
import sys
import time

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _env(**over):
    e = dict(os.environ, BENCH_SELFTEST="fake")
    e.update({k: str(v) for k, v in over.items()})
    return e


def _snapshots(stdout: str):
    """All parseable full-result lines, in order (the driver keeps the
    last parseable line — these are what a kill would leave behind)."""
    out = []
    for line in stdout.splitlines():
        try:
            d = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(d, dict) and "metric" in d:
            out.append(d)
    return out


def test_selftest_run_ships_partials_for_hang_and_error():
    """Full fake run: a hanging family is killed at the per-family
    timeout but its streamed partial survives; a crashing family is
    recorded as an error; every completed family is in the artifact."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        env=_env(BENCH_BUDGET_S=30, BENCH_FAMILY_TIMEOUT_S=2,
                 BENCH_SELFTEST_HANG_S=600, BENCH_SELFTEST_STEP_S=0.01),
        timeout=60)
    wall = time.monotonic() - t0
    snaps = _snapshots(proc.stdout)
    # one snapshot per fake family (6) plus the final line
    assert len(snaps) >= 7
    final = snaps[-1]
    fams = final["families"]
    assert fams["fast_a"] == {"v": 1}
    assert fams["fast_b"] == {"v": 2}
    assert fams["tail_z"] == {"v": 3}
    assert fams["slow_stream"]["step39"] == 39
    # the hang family timed out, but its streamed partial was kept
    assert fams["hang"] == {"streamed": "before-hang"}
    assert "timed out" in final["errors"]["hang"]
    assert "partial result kept" in final["errors"]["hang"]
    assert "ZeroDivisionError" in final["errors"]["boom"]
    # the hang was killed at ~2s, not 600s
    assert wall < 30


def test_budget_exhaustion_skips_tail_loudly():
    """A tight budget skips late families with a recorded reason, and
    wall-clock stays bounded by the budget, not by family count."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        env=_env(BENCH_BUDGET_S=3, BENCH_FAMILY_TIMEOUT_S=2,
                 BENCH_SELFTEST_HANG_S=600, BENCH_SELFTEST_STEP_S=0.2),
        timeout=60)
    wall = time.monotonic() - t0
    final = _snapshots(proc.stdout)[-1]
    assert wall < 20            # 6 families, none allowed to run long
    skipped = [k for k, v in final["errors"].items()
               if "budget" in str(v)]
    assert skipped, f"expected skipped families, errors={final['errors']}"
    # what ran before the budget ran out is still in the artifact
    assert final["families"].get("fast_a") == {"v": 1}


def test_implausibly_slow_cfg_retried_with_both_results_shipped(
        tmp_path):
    """A BASELINE-table config under the 30 FPS target is retried
    once; the artifact carries BOTH results."""
    state = tmp_path / "flaky_count"
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        env=_env(BENCH_BUDGET_S=60, BENCH_FAMILY_TIMEOUT_S=30,
                 BENCH_SELFTEST_HANG_S=0, BENCH_SELFTEST_STEP_S=0.01,
                 BENCH_SELFTEST_STATE=state),
        timeout=120)
    final = _snapshots(proc.stdout)[-1]
    flaky = final["families"]["cfg_flaky"]
    assert flaky["fps"] == 100.0
    assert flaky["slow_first_attempt"]["fps"] == 5.0


def test_sigkill_mid_run_leaves_parseable_snapshot():
    """SIGKILL (untrappable — the driver's last resort) at an arbitrary
    point: the last fully-printed snapshot line still carries every
    completed family."""
    proc = subprocess.Popen(
        [sys.executable, BENCH], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=_env(BENCH_BUDGET_S=60, BENCH_FAMILY_TIMEOUT_S=30,
                 BENCH_SELFTEST_HANG_S=0, BENCH_SELFTEST_STEP_S=0.3))
    # wait for the first snapshot (fast_a done), then SIGKILL mid-stream
    lines = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        if _snapshots(line):
            break
    proc.kill()
    rest, _ = proc.communicate(timeout=30)
    snaps = _snapshots("".join(lines) + rest)
    assert snaps, "no parseable snapshot survived the SIGKILL"
    assert snaps[-1]["families"].get("fast_a") == {"v": 1}
    assert snaps[-1].get("partial") is True


def test_partial_file_persisted_and_disableable(tmp_path):
    """Every snapshot is also atomically mirrored to BENCH_PARTIAL_PATH
    (round-4 regression: BENCH_r04 hit the driver's `timeout -k` with rc
    124 and shipped NOTHING — stdout dies with the terminal, a file
    survives). Empty path disables the mirror."""
    part = tmp_path / "part.json"
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        env=_env(BENCH_BUDGET_S=30, BENCH_FAMILY_TIMEOUT_S=2,
                 BENCH_SELFTEST_HANG_S=0, BENCH_SELFTEST_STEP_S=0.01,
                 BENCH_PARTIAL_PATH=part),
        timeout=60)
    with open(part) as f:
        saved = json.load(f)
    # the mirror carries the same cumulative artifact as stdout
    final = _snapshots(proc.stdout)[-1]
    assert saved["families"].get("fast_a") == {"v": 1}
    assert saved["families"] == final["families"]
    # no stray tmp file left behind by the atomic-replace dance
    assert list(tmp_path.iterdir()) == [part]

    off = tmp_path / "off.json"
    subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        env=_env(BENCH_BUDGET_S=30, BENCH_FAMILY_TIMEOUT_S=2,
                 BENCH_SELFTEST_HANG_S=0, BENCH_SELFTEST_STEP_S=0.01,
                 BENCH_PARTIAL_PATH=""),
        timeout=60)
    assert not off.exists()


def test_sigterm_partial_file_written_signal_safely(tmp_path):
    """SIGTERM mid-hang: the handler's os.write path leaves a parseable
    partial file even though normal emission never ran again."""
    part = tmp_path / "term.json"
    proc = subprocess.Popen(
        [sys.executable, BENCH], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=_env(BENCH_BUDGET_S=120, BENCH_FAMILY_TIMEOUT_S=60,
                 BENCH_SELFTEST_HANG_S=600, BENCH_SELFTEST_STEP_S=0.3,
                 BENCH_PARTIAL_PATH=part))
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        d = _snapshots(line)
        if d and "fast_b" in d[-1].get("families_done", []):
            proc.send_signal(signal.SIGTERM)
            break
    proc.communicate(timeout=30)
    assert proc.returncode == 3
    with open(part) as f:
        saved = json.load(f)
    assert saved["families"].get("fast_b") == {"v": 2}
    assert saved["errors"]["bench"] == "terminated by SIGTERM"


def test_sigterm_emits_final_snapshot():
    """SIGTERM (what `timeout` sends first): the handler reaps the
    in-flight child and prints a final cumulative snapshot before
    exiting."""
    proc = subprocess.Popen(
        [sys.executable, BENCH], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=_env(BENCH_BUDGET_S=120, BENCH_FAMILY_TIMEOUT_S=60,
                 BENCH_SELFTEST_HANG_S=600, BENCH_SELFTEST_STEP_S=0.3))
    saw = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        saw.append(line)
        d = _snapshots(line)
        # terminate while the hang family is in flight
        if d and "fast_b" in d[-1].get("families_done", []):
            proc.send_signal(signal.SIGTERM)
            break
    rest, _ = proc.communicate(timeout=30)
    assert proc.returncode == 3
    snaps = _snapshots("".join(saw) + rest)
    final = snaps[-1]
    assert final["errors"]["bench"] == "terminated by SIGTERM"
    assert final["families"].get("fast_a") == {"v": 1}
    assert final["families"].get("fast_b") == {"v": 2}
