"""docs/getting-started.md must not drift from reality.

Every `python -m nnstreamer_tpu '...'` command in the walkthrough is
extracted verbatim and executed as a real CLI subprocess (sanitized to
the CPU backend, same pattern as test_multihost.py); the doc's expected
outputs are asserted against the files the pipelines write."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "getting-started.md")
MODELS = "/root/reference/tests/test_models/models"

needs_models = pytest.mark.skipif(
    not os.path.exists(MODELS), reason="reference test models absent")


def _commands():
    text = open(DOC).read()
    # `python -m nnstreamer_tpu '<pipeline>' && cat <file>` lines
    pat = re.compile(
        r"python -m nnstreamer_tpu '([^']+)' && cat (\S+)")
    return pat.findall(text)


def _run_cli(pipeline: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TPU_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "nnstreamer_tpu", pipeline],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)


def test_doc_has_all_four_walkthrough_commands():
    cmds = _commands()
    assert len(cmds) == 4
    models = " ".join(p for p, _ in cmds)
    for needle in ("mobilenet_v2_1.0_224_quant.tflite",
                   "pytorch_lenet5.pt", "lenet_iter_9000.caffemodel",
                   "lenet5.uff"):
        assert needle in models


@needs_models
@pytest.mark.parametrize("idx,expected", [
    (0, "orange"), (1, "9"), (2, "9"), (3, "9")])
def test_walkthrough_command_produces_documented_output(
        idx, expected, tmp_path):
    pipeline, outfile = _commands()[idx]
    # keep the doc's /tmp paths out of parallel test runs' way
    private = str(tmp_path / os.path.basename(outfile))
    pipeline = pipeline.replace(outfile, private)
    proc = _run_cli(pipeline)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = open(private).read().strip()
    assert got == expected
