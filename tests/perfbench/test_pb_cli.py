"""The command itself: it fails off a TPU and prints no result."""

import json
import os
import subprocess
import sys

from perfbench import harness


def _run(args, cwd=harness.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py")]
        + args, cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_to_measure_off_a_tpu():
    cmd = harness.load_manifest()["command"]
    assert cmd == ["python3", "perfbench/run.py"]
    p = _run(["--workload", "ouro_chat_backlog", "--seed", str(2**31 + 5),
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{")            # no result line


def test_unknown_workload_fails_without_a_result():
    p = _run(["--workload", "nope", "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_is_one_json_object_with_the_contracts_keys():
    out = harness.Outcome(attempted=3, failed=0, end_to_end={}, checks=[])
    line = harness.result_line(
        True, out, {"setup_s": {"value": 1.25, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 5}, None)
    obj = json.loads(line)
    assert "\n" not in line
    assert list(obj) == ["correct", "attempted", "failed", "metrics",
                         "device"]
    traced = json.loads(harness.result_line(
        False, out, {}, {"platform": "tpu"},
        {"device_ops": [], "idle_gaps": []}))
    assert traced["correct"] is False and "breakdown" in traced


def test_checks_print_the_number_beside_its_limit():
    c = harness.at_most("served_token_gap[4req,700tok]", 0.012, 0.05)
    assert c.ok and c.line() == (
        "check served_token_gap[4req,700tok]: 0.012 limit 0.05 (ok)")
    assert "FAILED" in harness.at_most("x", 2, 1).line()
