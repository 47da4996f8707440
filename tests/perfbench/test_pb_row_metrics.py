"""The three readers of the engine's row account (ISSUE 37) against a
hand-made `ctx`: each is a share of the window's row-steps, and reads
nothing where the run's counters lack the keys (every run, until the
runners' `_counters()` carry them)."""

import pytest

from perfbench.layer_metrics import (rows_blocked_pct, rows_prefilling_pct,
                                     rows_unfed_pct)

READERS = {"rows_prefilling_pct": rows_prefilling_pct,
           "rows_blocked_pct": rows_blocked_pct,
           "rows_unfed_pct": rows_unfed_pct}

# 400 launches of 32 rows inside the window, after a warm-up that had
# its own: the readers see the window's share alone
START = {"row_steps_total": 3200, "row_steps_decode": 900,
         "row_steps_prefilling": 2000, "row_steps_blocked": 100,
         "row_steps_blocked_state": 0, "row_steps_unfed": 200,
         "decode_steps": 100}
END = {"row_steps_total": 16000, "row_steps_decode": 5060,
       "row_steps_prefilling": 9680, "row_steps_blocked": 420,
       "row_steps_blocked_state": 320, "row_steps_unfed": 520,
       "decode_steps": 500}
WANT = {"rows_prefilling_pct": 100.0 * 7680 / 12800,
        "rows_blocked_pct": 100.0 * (320 + 320) / 12800,
        "rows_unfed_pct": 100.0 * 320 / 12800}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_is_its_share_of_the_windows_row_steps(name):
    ctx = {"counters": {"start": START, "end": END}}
    assert READERS[name].read(ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_without_its_counters(name):
    read = READERS[name].read
    assert read({}) is None
    # what the runners' `_counters()` carry today
    old = {k: {"decode_steps": v["decode_steps"]} for k, v in
           (("start", START), ("end", END))}
    assert read({"counters": old}) is None
    # no launch inside the window: no share
    assert read({"counters": {"start": END, "end": END}}) is None
    one_end = {"counters": {"end": END}}
    assert read(one_end) is None


def test_rows_blocked_needs_both_kinds():
    lacks = {k: {key: v for key, v in snap.items()
                 if key != "row_steps_blocked_state"}
             for k, snap in (("start", START), ("end", END))}
    assert rows_blocked_pct.read({"counters": lacks}) is None
    assert rows_unfed_pct.read({"counters": lacks}) is not None
