"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it fails at once off a TPU, makes the weights and inputs from
the seed, starts the served path, warms the cell's own shapes (all of that
is ``setup_s``), measures for ``--seconds``, decides ``correct`` against the
plain reference outside the window, and prints one JSON object as the last
line of its output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` profiles part of the window and reports its per-layer metrics.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, xplane      # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def breakdown_of(readings: dict) -> dict:
    tw = readings["trace_window"]
    gaps = xplane.idle_gaps(tw.trace, tw.start, tw.end)
    return {"device_ops": xplane.top_device_ops(tw.trace, tw.start, tw.end),
            "idle_gaps": xplane.attribute_gaps(gaps, readings["host_spans"])}


def prepare(workload: str):
    """The cell, the chip and the compile cache: (cell, device, devices).
    Fails off a TPU."""
    cell = harness.resolve_cell(harness.load_manifest(), workload)
    device = harness.require_chip(cell.chips)
    import jax
    from nnstreamer_tpu.serving.compile_cache import enable_compile_cache

    # the program's own resolver: JAX_COMPILATION_CACHE_DIR where it is
    # set, else the fixed <checkout>/.jax_cache
    enable_compile_cache()
    return cell, device, jax.devices()[:cell.chips]


def serve(cell: harness.Cell, seed: int, seconds: float, trace: bool,
          devices, phases: harness.Phases):
    """Set up, drive the window, free the program's state: (runner, what
    the window observed, the device's peak memory)."""
    mod = importlib.import_module(f"perfbench.runners.{cell.config['runner']}")
    runner = mod.Runner(cell, seed, seconds, trace, devices)
    try:
        runner.setup(phases)
        obs = runner.window(phases)
        peak = harness.memory_peak_bytes(devices)
    finally:
        runner.teardown()
    return runner, obs, peak


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, device: dict, t_start: float) -> str:
    """Everything after the look for a chip; returns the result line."""
    phases = harness.Phases(t_start)
    phases.mark("imports_and_devices")
    runner, obs, peak = serve(cell, seed, seconds, trace, devices, phases)
    setup_s = obs["t0"] - t_start
    harness.log(phases.line())
    if getattr(runner, "warm_detail", None):
        harness.log("warm_detail " + json.dumps(runner.warm_detail))
    outcome = runner.outcome(obs)
    for c in outcome.checks:
        harness.log(c.line())
    correct = all(c.ok for c in outcome.checks) and outcome.failed == 0
    outcome.end_to_end["setup_s"] = setup_s
    device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        tw = outcome.readings["trace_window"]
        device["busy_s"] = xplane.busy_seconds(tw.trace, tw.start, tw.end)
        device["window_s"] = tw.end - tw.start
        ctx = dict(outcome.readings, config=cell.config, cell=cell,
                   device_kind=device["kind"])
        metrics = harness.read_layer_metrics(cell, ctx)
        breakdown = breakdown_of(outcome.readings)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise harness.HarnessError(
                    f"the run produced no {m['name']}")
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    return harness.result_line(correct, outcome, metrics, device, breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell, device, devices = prepare(args.workload)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        devices, device, _T_START)
    except harness.HarnessError as e:
        harness.warn(f"perfbench: {e}")
        return 3
    harness.log(line)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's daemon threads must not keep a finished run alive
    os._exit(code)
