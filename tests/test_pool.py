"""Supervised worker-pool tests: crash isolation, restart + circuit,
drain, hot-swap broadcast, and conservation across kills (ISSUE 9).

The chaos tests (marker `chaos`) SIGKILL/hang real child processes and
assert the supervision contract: every offered frame still ends as
exactly one of {replied, rejected, shed}, the pool returns to capacity
within the restart budget, and close() leaves zero orphans (psutil-free
/proc audit). They are tier-1 — fast, deterministic via injected chaos
hooks (WorkerSpec.crash_pts / hang_pts / crash_after_s) — but carry the
marker so a constrained CI lane can deselect them (`-m 'not chaos'`).
"""

import itertools
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.edge.query import QueryServer, TensorQueryServerSrc
from nnstreamer_tpu.serving.pool import (
    DISABLED, PooledQueryServer, WorkerPool, proc_alive)
from nnstreamer_tpu.serving.worker import WorkerSpec
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.traffic.loadgen import (
    poisson_arrivals, run_against_pool, run_open_loop)

_sid = itertools.count(7000)


@pytest.fixture(autouse=True)
def _clean_servers():
    yield
    QueryServer.reset_all()


def _conserved(c: dict) -> bool:
    return (c["offered"] == c["admitted"] + sum(c["rejected"].values())
            and c["admitted"] == c["replied"] + sum(c["shed"].values())
            + c["depth"] + c["inflight"])


def _echo_pool(**kw):
    kw.setdefault("workers", 2)
    kw.setdefault("service_ms", 2.0)
    return PooledQueryServer.echo(sid=next(_sid), **kw)


def _drive(pqs, n, rate_hz=100.0, **kw):
    """Open-loop load against a live pool; returns the SLO report."""
    x = np.ones((8, 1), np.float32)
    return run_open_loop(
        "127.0.0.1", pqs.port, dims="8:1",
        arrivals=poisson_arrivals(rate_hz, n),
        make_frame=lambda i: TensorBuffer.of(x, pts=i),
        p99_budget_ms=kw.pop("p99_budget_ms", 250.0), **kw)


# -- basics -------------------------------------------------------------------

class TestPoolBasics:
    def test_echo_round_trip_and_clean_close(self):
        pqs = _echo_pool()
        pool = pqs.pool
        try:
            rep = _drive(pqs, 40)
            assert rep["completed"] == 40 and rep["lost"] == 0
            assert _conserved(pqs.admission_counters())
            st = pool.stats()
            # least-outstanding routing: per-worker reply counters exist
            # and account for every completion
            assert sum(w["replied"] for w in st["workers"]) == 40
            assert {w["state"] for w in st["workers"]} == {"ready"}
        finally:
            pids = pool.all_pids_ever()
            pqs.close()
        assert pids and not any(proc_alive(p) for p in pids)

    def test_out_spec_adopted_from_worker_hello(self):
        pqs = _echo_pool(dims="4:1")
        try:
            assert pqs.qs.out_spec is not None
            dims, types, _ = pqs.qs.out_spec.to_strings()
            assert dims == "4:1"
        finally:
            pqs.close()

    def test_serversrc_extra_stats_merge_pool_view(self):
        pqs = _echo_pool()
        try:
            src = TensorQueryServerSrc(name="s", id=pqs.sid, dims="8:1")
            out = src.extra_stats()
            assert out["pool_workers"] == 2
            assert out["worker0_state"] == "ready"
            assert "worker1_restarts" in out
        finally:
            pqs.close()

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError, match="workers"):
            WorkerPool(QueryServer.get(next(_sid)), WorkerSpec(), 0)
        with pytest.raises(ValueError, match="kind"):
            WorkerSpec(kind="wat")
        with pytest.raises(ValueError, match="pipeline"):
            WorkerSpec(kind="pipeline")


# -- chaos: crash / hang / circuit -------------------------------------------

@pytest.mark.chaos
class TestCrashRecovery:
    def test_sigkill_mid_flood_conserves_and_recovers(self):
        """The ISSUE 9 acceptance smoke: 2-worker pool at 1.5x load,
        SIGKILL one worker mid-flood → zero lost frames, back at full
        capacity within the restart budget, zero orphans after close
        (/proc audit inside run_against_pool)."""
        rep = run_against_pool(
            n=160, service_ms=10.0, workers=2, load_x=1.5, kills=1,
            seed=3, max_pending=32, p99_budget_ms=90.0)
        assert rep["lost"] == 0
        assert rep["conserved"]
        assert rep["recovered"], rep["pool"]
        assert rep["orphans"] == []
        assert rep["kill_schedule"][0]["pid"] is not None
        assert rep["pool"]["pool"]["restarts"] >= 1
        assert rep["seed"] == 3

    def test_poison_frame_sheds_worker_lost_after_redelivery(self):
        """A frame that kills every worker that touches it must burn
        its redelivery budget and then be shed with BUSY(worker_lost) —
        not crash-loop the pool forever, not vanish in silence."""
        pqs = PooledQueryServer(
            WorkerSpec(kind="echo", service_ms=5.0, crash_pts=3),
            workers=1, sid=next(_sid), max_pending=32,
            restart_backoff_s=0.02)
        try:
            rep = _drive(pqs, 8, rate_hz=50.0, drain_timeout_s=20.0)
            assert rep["lost"] == 0
            assert rep["completed"] == 7
            assert rep["busy_causes"] == {"worker_lost": 1}
            c = pqs.admission_counters()
            assert c["shed"].get("worker_lost") == 1 and _conserved(c)
            # first delivery + one redelivery, each fatal
            assert pqs.pool.stats()["pool"]["restarts"] >= 2
        finally:
            pqs.close()

    def test_hang_detected_by_frame_deadline_not_heartbeat(self):
        """A worker wedged inside service keeps heartbeating (dedicated
        thread) — the per-frame liveness deadline is what must catch
        it, SIGKILL the worker, and shed the frame."""
        pqs = PooledQueryServer(
            WorkerSpec(kind="echo", service_ms=1.0, hang_pts=2),
            workers=1, sid=next(_sid), max_pending=32,
            frame_deadline_s=0.5, max_redeliver=0,
            per_worker_queue=1,   # only the hanging frame is in flight
            restart_backoff_s=0.02)
        try:
            rep = _drive(pqs, 5, rate_hz=100.0, drain_timeout_s=20.0)
            assert rep["lost"] == 0
            assert rep["completed"] == 4
            assert rep["busy_causes"] == {"worker_lost": 1}
            st = pqs.pool.stats()["pool"]
            assert st["kills"] >= 1        # SIGKILLed, not exited
            assert _conserved(pqs.admission_counters())
        finally:
            pqs.close()

    def test_restart_budget_circuit_degrades_instead_of_flapping(self):
        from nnstreamer_tpu.runtime.tracing import Tracer

        tracer = Tracer()
        pqs = PooledQueryServer(
            WorkerSpec(kind="echo", crash_after_s=0.05),
            workers=1, sid=next(_sid), tracer=tracer,
            restart_budget=2, restart_window_s=30.0,
            restart_backoff_s=0.01, ready_timeout_s=0.2)
        try:
            pool = pqs.pool
            deadline = time.monotonic() + 15
            while pool.degraded < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            st = pool.stats()
            assert st["pool"]["degraded"] == 1, st
            assert st["workers"][0]["state"] == DISABLED
            assert pool.live_workers() == 0
            # a tripped circuit stays tripped: no further restarts
            restarts = st["pool"]["restarts"]
            time.sleep(0.3)
            assert pool.stats()["pool"]["restarts"] == restarts
            # lifecycle surfaced through the tracer
            wc = tracer.summary()["workers"][pool.name]
            assert wc["degraded"] == 1 and wc["restart"] >= 2
        finally:
            pqs.close()


# -- hot swap -----------------------------------------------------------------

class TestPoolSwap:
    def test_two_phase_commit_bumps_epoch_on_all_workers(self):
        pqs = _echo_pool()
        try:
            rep = pqs.swap("m", 1)
            assert rep["ok"] and rep["epoch"] == 1
            assert all(w["prepare_ok"] and w["commit_ok"]
                       for w in rep["workers"].values())
            assert len(rep["workers"]) == 2
        finally:
            pqs.close()

    def test_prepare_failure_aborts_all_epoch_unchanged(self):
        pqs = PooledQueryServer(
            WorkerSpec(kind="echo", service_ms=1.0,
                       swap_fail_version=9),
            workers=2, sid=next(_sid))
        try:
            assert pqs.swap("m", 1)["ok"] and pqs.pool.epoch == 1
            rep = pqs.swap("m", 9)        # injected prepare failure
            assert not rep["ok"]
            assert pqs.pool.epoch == 1    # all-or-none: did not move
            # pool still serves after the aborted swap
            assert _drive(pqs, 10)["completed"] == 10
        finally:
            pqs.close()


# -- drain / close ------------------------------------------------------------

class TestPoolDrain:
    def test_close_drains_inflight_within_budget(self):
        pqs = _echo_pool(service_ms=30.0)
        qs = pqs.qs
        try:
            x = np.ones((8, 1), np.float32)
            for i in range(4):
                assert qs.frames.offer(TensorBuffer.of(x, pts=i)
                                       .with_meta(client_id=1)).admitted
            time.sleep(0.15)              # router dispatches them
        finally:
            pqs.close()
        c = qs.frames.counters()
        # drained, not shed: the frames finished inside the drain budget
        assert c["replied"] == 4 and c["shed"] == {} and _conserved(c)

    def test_close_is_idempotent(self):
        pqs = _echo_pool()
        pqs.close()
        before = pqs.qs.frames.counters()
        pqs.close()                       # second close: strict no-op
        assert pqs.qs.frames.counters() == before
        assert pqs.pool.closed

    def test_close_sheds_queued_frames_as_shutdown(self):
        # no client draining replies, workers too slow to finish:
        # whatever cannot complete inside the drain budget must be shed
        # with a typed cause, never silently dropped
        pqs = _echo_pool(workers=1, service_ms=200.0,
                         drain_timeout_s=0.2)
        qs = pqs.qs
        x = np.ones((8, 1), np.float32)
        for i in range(6):
            qs.frames.offer(TensorBuffer.of(x, pts=i)
                            .with_meta(client_id=1))
        time.sleep(0.05)
        pqs.close()
        c = qs.frames.counters()
        assert _conserved(c) and c["depth"] == 0 and c["inflight"] == 0
        assert c["replied"] + c["shed"].get("shutdown", 0) == 6


@pytest.mark.chaos
class TestNoOrphans:
    def test_two_worker_pool_kill_one_recover_zero_orphans(self):
        """ISSUE 9 satellite: tier-1 smoke — boot a 2-worker pool,
        SIGKILL one, assert recovery and zero orphans via a psutil-free
        /proc check over every pid the pool ever spawned."""
        pqs = _echo_pool(restart_backoff_s=0.02)
        pool = pqs.pool
        try:
            killed = pool.kill_worker()
            assert killed is not None
            # wait for the supervisor to notice, reap, and respawn
            deadline = time.monotonic() + 10
            while pool.stats()["pool"]["restarts"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert pool.stats()["pool"]["restarts"] == 1, pool.stats()
            assert pool.wait_ready(10.0), pool.stats()
            rep = _drive(pqs, 20)
            assert rep["completed"] == 20 and rep["lost"] == 0
        finally:
            pids = pool.all_pids_ever()
            pqs.close()
        assert len(pids) == 3             # 2 initial + 1 restart
        assert not any(proc_alive(p) for p in pids)


# -- distributed tracing across the pool (ISSUE 11) --------------------------

@pytest.mark.chaos
class TestPoolTracing:
    def test_redelivery_keeps_trace_id_across_workers(self):
        """ISSUE 11 regression: a frame redelivered after a worker
        SIGKILL keeps its ORIGINAL trace id — the merged timeline shows
        the dead worker's dispatch hop AND the replacement's, under one
        id. A fresh id on re-offer would sever the two attempts."""
        from nnstreamer_tpu.runtime.tracing import Tracer, hop_spans

        tr = Tracer()
        rep = run_against_pool(
            n=160, service_ms=15.0, workers=2, load_x=1.8, kills=1,
            seed=3, max_pending=32, p99_budget_ms=400.0, trace=True,
            tracer=tr)
        assert rep["lost"] == 0
        assert rep["conserved"]
        assert rep["orphans"] == []
        assert rep["pool"]["pool"]["reoffered"] >= 1, \
            "kill landed on an idle worker: no redelivery to test"
        redelivered = []
        for name, tid, t, hops, args in tr.requests():
            disp = [h for h in hops if h.get("hop") == "dispatch"]
            if len(disp) >= 2:
                redelivered.append((tid, hops, disp))
        assert redelivered, "no completed request carries 2 dispatches"
        for tid, hops, disp in redelivered:
            hop_names = [h["hop"] for h in hops]
            assert "reoffer" in hop_names
            # both attempts live under the one id: the dead worker's
            # pid (captured by the parent at dispatch time) differs
            # from the replacement's
            wpids = {h.get("wpid") for h in disp}
            assert len(wpids) == 2, (tid, disp)
            spans = hop_spans(hops)
            assert spans["redeliveries"] >= 1
            # stage math comes from the attempt that replied
            assert spans.get("service_ms", 0) > 0

    def test_worker_tracers_merge_into_pool_summary(self):
        """Each worker's own Tracer ships deltas over the heartbeat
        lane; the parent merges them into one summary and one Chrome
        trace with a track group per worker process."""
        from nnstreamer_tpu.runtime.tracing import (
            Tracer, ensure_trace_ctx)

        tr = Tracer()
        pqs = _echo_pool(service_ms=2.0, tracer=tr)
        try:
            x = np.ones((8, 1), np.float32)

            def mk(i):
                b = TensorBuffer.of(x, pts=i)
                ensure_trace_ctx(b.meta)
                return b

            rep = run_open_loop(
                "127.0.0.1", pqs.port, dims="8:1",
                arrivals=poisson_arrivals(150.0, 30),
                make_frame=mk, p99_budget_ms=500.0)
            assert rep["completed"] == 30 and rep["lost"] == 0
            # heartbeat interval bounds how long a delta can lag
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                kids = tr.children()
                if kids and sum(k["events_total"]
                                for k in kids.values()) >= 30:
                    break
                time.sleep(0.05)
            kids = tr.children()
            assert kids, "no worker shipped a trace delta"
            assert sum(k["events_total"] for k in kids.values()) >= 30
            # per-element histograms arrive namespaced per worker
            hists = tr.hists()
            assert any(n.startswith("w") and n.endswith("/echo")
                       for n in hists)
            assert sum(h["count"] for n, h in hists.items()
                       if "/echo" in n) == 30
            # one process track group per live worker in the export
            doc = tr.to_chrome_trace("pool")
            pids = {e["pid"] for e in doc["traceEvents"]}
            assert len(pids) >= 1 + len(kids)
            # request timelines span admission -> worker -> reply
            assert any(
                {"admit", "worker_recv", "reply"} <=
                {h.get("hop") for h in hops}
                for _, _, _, hops, _ in tr.requests())
        finally:
            pqs.close()


# -- serve CLI: one process per chip -----------------------------------------

def test_serve_supervisor_never_loads_jax(tmp_path):
    """`serve --metrics-port --flight-dir`: the supervisor answers a
    scrape and drains without ever loading jaxlib — it initialises no
    backend, so it holds no chip for its workers to fight over."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "nnstreamer_tpu", "serve", "--workers", "1",
         "--metrics-port", "0", "--flight-dir", str(tmp_path / "flight")],
        stderr=subprocess.PIPE, text=True, cwd=root)
    watchdog = threading.Timer(60.0, proc.kill)
    watchdog.start()
    try:
        port, seen = None, []
        for line in proc.stderr:
            seen.append(line)
            m = re.search(r"metrics on http://[\d.]+:(\d+)/metrics", line)
            if m:
                port = int(m.group(1))
            if "pool serving on" in line:
                break
        assert port is not None, "".join(seen)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200 and b"nns_" in r.read()
        with open(f"/proc/{proc.pid}/maps") as f:
            maps = f.read()
        assert "jaxlib" not in maps and "libtpu" not in maps
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(30) == 0
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
