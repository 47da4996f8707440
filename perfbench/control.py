"""The control of ``correct``: runs of a cell, and the same comparison with
the reference computed one precision below the configuration's own.

    python3 perfbench/control.py --workload <name> --seeds <a,b,c> --seconds <s>

For each seed, in one process, it drives the cell exactly as ``run.py``
does (same runner, same load, same sample, same checks), then reads each
number a limit may be set on twice over the same sample: for what the
program served, and for what the reference puts first, teacher-forced over
the same prompts and tokens, in each lower precision the configuration file
lists under ``check.controls``.  The limits in the configuration files were
set between the two readings; PERF.md lists them.  The benchmark's own runs
never run this, and it prints no result line.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, run      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers separated by commas")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        cell, _, devices = run.prepare(args.workload)
        for seed in (int(s) for s in args.seeds.split(",")):
            runner, obs, _ = run.serve(cell, seed, args.seconds, False,
                                       devices, harness.Phases(_T_START))
            outcome = runner.outcome(obs)
            for c in outcome.checks:
                harness.log(c.line())
            readings = runner.control_readings(
                obs, cell.config["check"]["controls"])
            harness.log("control " + json.dumps(
                {"workload": args.workload, "seed": seed,
                 "seconds": args.seconds, "readings": readings}))
            del runner, obs, outcome
            gc.collect()
    except harness.HarnessError as e:
        harness.warn(f"perfbench: {e}")
        return 3
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
