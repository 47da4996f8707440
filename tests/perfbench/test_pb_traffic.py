"""Traffic is fixed by the cell's file: every seed offers the same work."""

import os
import time

import pytest

from perfbench import harness, traffic

MANIFEST = harness.load_manifest()
# every mix in the directory, one that no cell uses yet too
MIXES = sorted(f[:-5] for f in os.listdir(
    os.path.join(harness.ROOT, "perfbench", "mixes")) if f.endswith(".json"))
SEEDS = [0, 7, 2**31 + 12345, 2**32 + 99]


def test_every_cells_mix_has_its_file():
    assert {w["traffic"] for w in MANIFEST["workloads"]} <= set(MIXES)
    # data only: a mix directory with code in it would shadow the generator
    assert all(f.endswith(".json") for f in os.listdir(
        os.path.join(harness.ROOT, "perfbench", "mixes")))


@pytest.mark.parametrize("mix", MIXES)
def test_same_offered_work_for_every_seed(mix):
    tr = traffic.load(mix)
    seconds = float(MANIFEST["run_seconds"])
    work = [traffic.offered_work(tr, traffic.schedule(tr, s, seconds))
            for s in SEEDS]
    assert all(w == work[0] for w in work[1:])
    assert work[0]["n"] > 0


@pytest.mark.parametrize("mix", MIXES)
def test_seed_changes_the_order_only(mix):
    tr = traffic.load(mix)
    a = traffic.schedule(tr, 1, 10.0)
    b = traffic.schedule(tr, 2, 10.0)
    assert len(a) == len(b)
    assert a == traffic.schedule(tr, 1, 10.0)       # same seed, same inputs
    assert a != b


@pytest.mark.parametrize("mix", MIXES)
def test_backlog_is_a_sequence_of_whole_multisets(mix):
    tr = traffic.load(mix)
    arr = traffic.schedule(tr, 9, 30.0)
    n = len(tr["items"])
    assert len(arr) % n == 0
    assert len(arr) >= tr["arrival"]["base"] + tr["arrival"]["per_second"] * 30
    assert all(a.due_s == -tr["arrival"]["ramp_s"] for a in arr)
    for i in range(0, len(arr), n):
        assert sorted(a.item for a in arr[i:i + n]) == list(range(n))


def test_unknown_arrival_mode_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule({"arrival": {"mode": "nope"}, "items": [[1, 1]]},
                         1, 1.0)


def test_drive_sends_on_schedule_and_reports_lag():
    arr = [traffic.Arrival(0.02 * i, i) for i in range(10)]
    sent = []
    t0 = time.perf_counter() + 0.01
    lag = harness.drive(arr, t0, lambda a: sent.append(time.perf_counter()))
    assert len(lag) == 10 and min(lag) >= 0.0
    assert all(s >= t0 + a.due_s for s, a in zip(sent, arr))
    cut = harness.drive(arr, time.perf_counter(), lambda a: None,
                        until=time.perf_counter() + 0.05)
    assert len(cut) < 10
