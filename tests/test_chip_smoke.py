"""chip_smoke.py off the chip: it refuses, at once, and says what it found.

The pass itself can only happen on a TPU host (the builder's chip tool, the
driver's chip check); tier-1 pins the other half of the contract — there is
no CPU mode, no interpret mode and no result line anywhere but on the chip.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_off_chip_and_names_the_platform():
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode not in (0, None)
    assert "platform 'cpu'" in proc.stderr
    # no result: nothing on stdout parses as the ok line
    for line in proc.stdout.splitlines():
        try:
            assert not json.loads(line).get("ok")
        except (ValueError, AttributeError):
            pass
    assert "leg " not in proc.stdout          # no leg even started


def test_the_sparse_moe_leg_runs_where_both_devices_are_the_cpu():
    """The leg's own logic (the pipeline against the bare engine, the
    bundle's layout, its assertions) on the only device there is here."""
    sys.path.insert(0, REPO)
    import chip_smoke

    out = chip_smoke.leg_llm_sparse_moe()
    assert out["requests"] == 4 and out["tokens"] == 32
    assert out["chunk_prefills"] >= 10
