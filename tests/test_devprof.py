"""Device performance plane + SLO-breach flight recorder
(runtime/devprof.py, runtime/flightrec.py, docs/observability.md).

The contracts that matter: MFU math against an injected peak table
(declared peak → mfu; no peak → mfu 0 + measured calibration), the
dispatch→device_sync sampling choke point, one-scrape export of every
``nns_jit_*`` / ``nns_invoke_*`` / ``nns_device_*`` family with the
invoke-seconds ledger reconcilable against what was sampled, and the
flight recorder's forensic guarantees — exactly one complete bundle
per trigger within a cooldown window, never a partial bundle visible,
nothing at steady state.
"""

import json
import os
import threading

import numpy as np
import pytest

from nnstreamer_tpu.runtime import devprof
from nnstreamer_tpu.runtime.devprof import (
    DeviceProfiler, bucket_label, peak_for)
from nnstreamer_tpu.runtime.flightrec import (
    FlightRecorder, list_bundles, load_bundle)
from nnstreamer_tpu.runtime.sync import device_sync
from nnstreamer_tpu.runtime.tracing import NULL_TRACER, Tracer
from nnstreamer_tpu.serving.metrics import (
    metrics_snapshot, parse_prometheus, render_prometheus)


# -- profiler core -----------------------------------------------------------

class TestDeviceProfiler:
    def test_disabled_profiler_records_nothing(self):
        p = DeviceProfiler()
        p.note_compile("f", "b", seconds=1.0, flops=10.0)
        p.note_dispatch("f", "b")
        p.sample_sync()
        p.note_invoke("f", "b", 0.5)
        st = p.stats()
        assert st["enabled"] is False
        assert st["jit"] == [] and st["invoke"] == []

    def test_compile_registry_overwrites_cost_accumulates_seconds(self):
        p = DeviceProfiler().enable()
        p.note_compile("f", "b", seconds=1.0, flops=100.0,
                       bytes_accessed=50.0)
        p.note_compile("f", "b", seconds=0.5, flops=200.0)
        (row,) = p.stats()["jit"]
        # flops are a property of the program: last estimate wins;
        # wall seconds are spend: they add up
        assert row["flops"] == 200.0 and row["bytes_accessed"] == 50.0
        assert row["compile_s"] == pytest.approx(1.5)
        assert row["compiles"] == 2

    def test_mfu_and_roofline_against_injected_peak(self):
        # 100 TFLOP/s peak, 1000 GB/s peak -> ridge = 100e12/1000e9
        # = 100 flops/byte
        p = DeviceProfiler(peak_tflops=100.0, peak_hbm_gbps=1000.0)
        p.enable()
        # compute-bound bucket: ai = 2e12/1e9 = 2000 >= ridge
        p.note_compile("f", "hot", seconds=0.1, flops=2e12,
                       bytes_accessed=1e9)
        # memory-bound bucket: ai = 1e9/1e9 = 1 < ridge
        p.note_compile("f", "cold", seconds=0.1, flops=1e9,
                       bytes_accessed=1e9)
        for _ in range(5):
            p.note_invoke("f", "hot", 0.040)   # 2e12/0.04 = 50 TFLOP/s
            p.note_invoke("f", "cold", 0.010)
        st = p.stats()
        by_bucket = {r["bucket"]: r for r in st["jit"]}
        assert by_bucket["hot"]["roofline"] == "compute"
        assert by_bucket["cold"]["roofline"] == "memory"
        inv = {r["bucket"]: r for r in st["invoke"]}
        assert inv["hot"]["achieved_tflops"] == pytest.approx(50.0)
        assert inv["hot"]["mfu"] == pytest.approx(0.5)
        assert inv["hot"]["seconds_total"] == pytest.approx(0.2)
        assert inv["hot"]["samples_total"] == 5

    def test_cpu_fallback_mfu_zero_calibrated_set(self):
        # no declared peak (CPU emulation): mfu must report 0 — never a
        # made-up denominator — and mfu_calibrated ratios against the
        # best achieved TFLOP/s so buckets stay comparable
        p = DeviceProfiler(peak_tflops=0.0, peak_hbm_gbps=0.0).enable()
        p.note_compile("f", "fast", seconds=0.1, flops=1e9)
        p.note_compile("f", "slow", seconds=0.1, flops=1e9)
        p.note_invoke("f", "fast", 0.001)
        p.note_invoke("f", "slow", 0.002)
        st = p.stats()
        inv = {r["bucket"]: r for r in st["invoke"]}
        assert all(r["mfu"] == 0.0 for r in st["invoke"])
        assert inv["fast"]["mfu_calibrated"] == pytest.approx(1.0)
        assert inv["slow"]["mfu_calibrated"] == pytest.approx(0.5)
        assert {r["roofline"] for r in st["jit"]} == {"unknown"}
        assert st["calibration_tflops"] > 0

    def test_peak_table_prefix_match(self):
        assert peak_for("TPU v4") == (275.0, 1228.0)
        assert peak_for("TPU v5e") == (197.0, 819.0)
        assert peak_for("TPU v4 pod slice")[0] == 275.0
        assert peak_for("cpu") == (0.0, 0.0)
        assert peak_for("") == (0.0, 0.0)

    def test_bucket_label_forms(self):
        assert bucket_label(()) == "static"
        assert bucket_label(
            ("fix", ((1, 224, 224, 3), "uint8"), "x")) == \
            "fix:1x224x224x3"
        assert bucket_label(("dynb", 8, "y")) == "dynb:8"

    def test_dispatch_sample_closed_by_device_sync(self):
        # the choke-point contract: a thread-local dispatch stamp is
        # closed by the next device_sync on the same thread
        import jax

        prof = devprof.get()
        prof.reset()
        prof.enable(True)
        try:
            x = jax.device_put(np.ones((4,), np.float32))
            prof.note_dispatch("filt", "b")
            device_sync((x,), forced=True)
            st = prof.stats()
            (row,) = st["invoke"]
            assert (row["filter"], row["bucket"]) == ("filt", "b")
            assert row["samples_total"] == 1
            # no pending stamp -> the next sync takes no sample
            device_sync((x,), forced=True)
            assert prof.stats()["invoke"][0]["samples_total"] == 1
        finally:
            prof.enable(False)
            prof.reset()

    def test_sample_is_per_thread(self):
        p = DeviceProfiler().enable()
        p.note_dispatch("f", "b")
        closed = []

        def other():
            p.sample_sync()          # no stamp on THIS thread
            closed.append(p.stats()["invoke"])

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert closed == [[]]        # the other thread took no sample
        p.sample_sync()
        assert p.stats()["invoke"][0]["samples_total"] == 1

    def test_capture_cost_reads_xla_cost_model(self):
        import jax

        p = DeviceProfiler().enable()
        jitted = jax.jit(lambda a, b: a @ b)
        x = np.ones((8, 8), np.float32)
        jitted(x, x)                 # compile
        p.capture_cost("f", "mm", jitted, (x, x), seconds=0.01)
        (row,) = p.stats()["jit"]
        assert row["flops"] > 0      # 8x8x8 matmul: cost model saw it
        assert row["compile_s"] == pytest.approx(0.01)

    def test_capture_cost_failure_degrades_to_seconds_only(self):
        p = DeviceProfiler().enable()
        p.capture_cost("f", "b", object(), (1,), seconds=0.25)
        (row,) = p.stats()["jit"]
        assert row["flops"] == 0.0
        assert row["compile_s"] == pytest.approx(0.25)

    def test_model_attribution_rows_and_weakref_release(self):
        class Backend:
            def resident_bytes(self):
                return 1234

        p = DeviceProfiler().enable()
        be = Backend()
        p.attach_model("m", be)
        rows = [r for r in p.hbm_rows() if r["kind"] == "model:m"]
        assert rows and rows[0]["bytes"] == 1234.0
        del be                       # released model leaves the ledger
        assert not [r for r in p.hbm_rows() if r["kind"] == "model:m"]

    def test_counter_tracks_shapes(self):
        p = DeviceProfiler(peak_tflops=100.0).enable()
        p.note_compile("f", "b", seconds=0.1, flops=1e12)
        p.note_invoke("f", "b", 0.1)
        names = [n for n, _ in p.counter_tracks()]
        assert "mfu:f/b" in names


# -- one-scrape exposition ---------------------------------------------------

def _plane(peak=100.0, bw=1000.0):
    p = DeviceProfiler(peak_tflops=peak, peak_hbm_gbps=bw).enable()
    p.note_compile('we"ird\\f', "b:1", seconds=0.5, flops=2e12,
                   bytes_accessed=1e9)
    for _ in range(3):
        p.note_invoke('we"ird\\f', "b:1", 0.040)
    return p


class TestExposition:
    FAMILIES = ("nns_jit_flops", "nns_jit_bytes_accessed",
                "nns_jit_roofline_info", "nns_compile_seconds_total",
                "nns_compiles_total", "nns_invoke_mfu",
                "nns_invoke_mfu_calibrated", "nns_invoke_tflops",
                "nns_invoke_seconds_total", "nns_invoke_samples_total",
                "nns_device_hbm_bytes", "nns_device_hbm_headroom",
                "nns_device_peak_tflops",
                "nns_device_calibration_tflops")

    def test_every_family_round_trips_with_type_and_help(self):
        text = render_prometheus(metrics_snapshot(
            devprof=_plane().stats()))
        parsed = parse_prometheus(text)
        for fam in self.FAMILIES:
            assert fam in parsed, f"family {fam} missing"
            assert parsed[fam].get("type"), f"no TYPE for {fam}"
            assert parsed[fam].get("help"), f"no HELP for {fam}"
        assert parsed["nns_compile_seconds_total"]["type"] == "counter"
        assert parsed["nns_invoke_seconds_total"]["type"] == "counter"
        assert parsed["nns_invoke_mfu"]["type"] == "gauge"

    def test_label_escaping_round_trips(self):
        text = render_prometheus(metrics_snapshot(
            devprof=_plane().stats()))
        # the filter name carries a quote and a backslash; a scraper
        # must see them escaped, and the parser must round-trip them
        assert '\\"' in text and "\\\\" in text
        parsed = parse_prometheus(text)
        keys = list(parsed["nns_jit_flops"]["samples"])
        # the parser keeps the exposition (escaped) form of the key
        assert any('we\\"ird\\\\f' in k for k in keys), keys

    def test_counters_monotone_across_scrapes(self):
        p = _plane()
        s1 = parse_prometheus(render_prometheus(
            metrics_snapshot(devprof=p.stats())))
        p.note_invoke('we"ird\\f', "b:1", 0.040)
        p.note_compile('we"ird\\f', "b:1", seconds=0.1, flops=2e12)
        s2 = parse_prometheus(render_prometheus(
            metrics_snapshot(devprof=p.stats())))
        for fam in ("nns_compile_seconds_total", "nns_compiles_total",
                    "nns_invoke_seconds_total",
                    "nns_invoke_samples_total"):
            for k, v1 in s1[fam]["samples"].items():
                assert s2[fam]["samples"][k] >= v1, fam

    def test_invoke_seconds_reconcile_with_sampled_ledger(self):
        # the reconciliation contract: Σ nns_invoke_seconds_total from
        # ONE scrape equals exactly the device-seconds the profiler
        # sampled — the same observations a tracer proctime sum is
        # made of when both planes watch the same sync-latency filter
        p = DeviceProfiler(peak_tflops=100.0).enable()
        tr = Tracer()
        durations = [0.010, 0.020, 0.015, 0.040]
        t = 0.0
        for d in durations:
            p.note_invoke("f", "b", d)
            tr.record_process("f", None, t, t + d)
            t += d
        text = render_prometheus(metrics_snapshot(
            tracer=tr, devprof=p.stats()))
        parsed = parse_prometheus(text)
        inv = sum(v for k, v in
                  parsed["nns_invoke_seconds_total"]["samples"].items())
        proc = [v for k, v in
                parsed["nns_element_proctime_seconds"]["samples"].items()
                if k.endswith("_sum}") or "_sum{" in k]
        assert inv == pytest.approx(sum(durations), rel=1e-6)
        assert proc and proc[0] == pytest.approx(inv, rel=1e-6)

    def test_top_families_include_new_rows(self):
        from nnstreamer_tpu.serving.metrics import _TOP_KEY_FAMILIES

        for fam in ("nns_llm_tokens_total", "nns_llm_kernel_invokes_total",
                    "nns_llm_prefilling", "nns_invoke_mfu",
                    "nns_device_hbm_headroom"):
            assert fam in _TOP_KEY_FAMILIES


# -- backend integration -----------------------------------------------------

class TestBackendCapture:
    def test_xla_backend_reports_compile_and_invoke(self):
        from nnstreamer_tpu.backends.xla import XLABackend

        prof = devprof.get()
        prof.reset()
        prof.enable(True)
        try:
            be = XLABackend()
            be.open({"model": "zoo://mobilenet_v2", "custom": ""})
            x = np.zeros((1, 224, 224, 3), np.uint8)
            for _ in range(2):
                out = be.invoke((x,))
                device_sync(out, forced=True)
            st = prof.stats()
            (jit,) = st["jit"]
            assert jit["compiles"] == 1          # bucket cache: one compile
            assert jit["flops"] > 0 and jit["bytes_accessed"] > 0
            assert st["invoke"][0]["samples_total"] >= 1
            # executor-level HBM attribution row present
            assert any(r["kind"].startswith("model:")
                       for r in st["hbm"])
            be.close()
        finally:
            prof.enable(False)
            prof.reset()

    def test_profiler_off_is_default_and_free(self):
        prof = devprof.get()
        assert prof.enabled is False


# -- flight recorder ---------------------------------------------------------

class TestFlightRecorder:
    def _rec(self, tmp_path, **kw):
        clock = [0.0]
        rec = FlightRecorder(str(tmp_path), cooldown_s=60.0,
                             clock=lambda: clock[0], **kw)
        return rec, clock

    def test_steady_state_produces_no_bundle(self, tmp_path):
        rec, _ = self._rec(tmp_path)
        ok = {"offered": 10, "replied": 7, "rejected": {"b": 1},
              "shed": {}, "depth": 1, "inflight": 1}
        for _ in range(5):
            assert rec.scan(admission=ok, p99_ms=50.0,
                            p99_budget_ms=100.0) == []
        assert list_bundles(str(tmp_path)) == []
        assert rec.stats()["dumps_total"] == 0

    def test_slo_breach_one_bundle_per_cooldown_window(self, tmp_path):
        rec, clock = self._rec(tmp_path)
        p1 = rec.note_slo_breach(120.0, 100.0)
        assert p1 and os.path.isdir(p1)
        # within the window: suppressed, counted, no second bundle
        assert rec.note_slo_breach(130.0, 100.0) is None
        assert len(list_bundles(str(tmp_path))) == 1
        clock[0] += 61.0
        assert rec.note_slo_breach(140.0, 100.0) is not None
        assert len(list_bundles(str(tmp_path))) == 2
        st = rec.stats()
        assert st["dumps"]["slo_breach"] == 2
        assert st["suppressed"]["slo_breach"] == 1

    def test_conservation_needs_two_consecutive_scans(self, tmp_path):
        rec, _ = self._rec(tmp_path)
        bad = {"offered": 10, "replied": 5, "rejected": {}, "shed": {},
               "depth": 1, "inflight": 1}
        ok = dict(bad, replied=8)
        assert rec.scan(admission=bad) == []       # first mismatch: slack
        assert rec.scan(admission=ok) == []        # match resets streak
        assert rec.scan(admission=bad) == []
        fired = rec.scan(admission=bad)            # second consecutive
        assert fired == ["conservation"]
        b = list_bundles(str(tmp_path))
        assert [x["kind"] for x in b] == ["conservation"]
        assert b[0]["cause"]["consecutive_scans"] == 2

    def test_watermarked_triggers_baseline_first_observation(self,
                                                             tmp_path):
        rec, _ = self._rec(tmp_path)
        # historical faults at attach time must NOT dump
        wc = {"pool": {"kill": 3}}
        assert rec.scan(worker_counts=wc) == []
        # a RISE past the watermark does
        assert rec.scan(worker_counts={"pool": {"kill": 4}}) == \
            ["worker_fence"]
        # same for watchdog incidents
        wd = {"el": {"stall": 2}}
        assert rec.scan(watchdog_counts=wd) == []
        assert rec.scan(watchdog_counts={"el": {"stall": 3}}) == \
            ["watchdog"]

    def test_bundle_is_complete_and_atomic(self, tmp_path):
        rec, _ = self._rec(tmp_path)
        tr = Tracer()
        tr.record_process("el", None, 0.0, 0.01)
        rec.attach(tracer=tr, prom=lambda: "# scrape\n",
                   env=lambda: {"k": "v"})
        rec.tick({"gauge": 1})
        path = rec.trigger("manual", {"why": "test"})
        # no temp residue, no dot-entries visible
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(".")]
        b = load_bundle(path)
        assert b["cause"]["kind"] == "manual"
        assert b["cause"]["cause"] == {"why": "test"}
        assert b["env"] == {"k": "v"}
        assert b["metrics.prom"] == "# scrape\n"
        assert b["snapshots"][0]["snapshot"] == {"gauge": 1}
        assert any(ev.get("ph") for ev in b["trace"]["traceEvents"])
        # ... and the dump itself is on the tracer's keep-whole record
        assert [k for k, _, _ in tr.flight_dumps()] == ["manual"]

    def test_failed_dump_does_not_eat_the_cooldown(self, tmp_path,
                                                   monkeypatch):
        rec, _ = self._rec(tmp_path)

        def boom(*a, **k):
            raise OSError("disk full")

        monkeypatch.setattr(rec, "_dump", boom)
        with pytest.raises(RuntimeError):
            rec.trigger("manual", {})
        monkeypatch.undo()
        # the window was not consumed: the next trigger dumps
        assert rec.trigger("manual", {}) is not None

    def test_list_bundles_ignores_dot_and_foreign_entries(self,
                                                          tmp_path):
        rec, _ = self._rec(tmp_path)
        rec.trigger("manual", {})
        os.makedirs(str(tmp_path / ".tmp-flight-9999-manual-1"))
        os.makedirs(str(tmp_path / "not-a-bundle"))
        (tmp_path / "flight-0002-file").write_text("not a dir")
        names = [b["name"] for b in list_bundles(str(tmp_path))]
        assert names == ["flight-0001-manual"]

    def test_autotuner_feeds_slo_breaches(self, tmp_path):
        from nnstreamer_tpu.serving.autotune import AutoTuner, SLOSpec

        class P99Tracer:
            active = True

            def tenant_summary(self):
                return {"t0": {"p99_ms": 250.0}}

        rec, clock = self._rec(tmp_path)
        tuner = AutoTuner(SLOSpec(p99_budget_ms=100.0),
                          tracer=P99Tracer())
        rec.attach(autotune=tuner)
        assert tuner.flight is rec        # attach wires the feed
        tuner.tick()
        b = list_bundles(str(tmp_path))
        assert [x["kind"] for x in b] == ["slo_breach"]
        assert b[0]["cause"]["p99_ms"] == 250.0
        tuner.tick()                      # cooldown: still one bundle
        assert len(list_bundles(str(tmp_path))) == 1

    def test_poll_reads_attached_tracer_counters(self, tmp_path):
        rec, _ = self._rec(tmp_path)
        tr = Tracer()
        rec.attach(tracer=tr)
        # first nonzero observation per source only baselines
        tr.record_worker_event("pool", 0, "kill", 0.0)
        tr.record_watchdog("el", "stall", 0.0)
        assert rec.poll() == []
        tr.record_worker_event("pool", 1, "fence", 1.0)
        assert "worker_fence" in rec.poll()
        tr.record_watchdog("el", "stall", 2.0)
        assert "watchdog" in rec.poll()
        # benign lifecycle kinds (spawn/ready) never count as faults
        tr.record_worker_event("pool", 2, "spawn", 3.0)
        assert rec.poll() == []


# -- tracer hooks ------------------------------------------------------------

class TestTracerHooks:
    def test_null_tracer_twins_noop(self):
        # flightrec + devprof call these unguarded on whatever tracer
        # is wired; the null twin must absorb every one
        NULL_TRACER.record_flight("manual", 0.0, path="/x")
        NULL_TRACER.record_watchdog("el", "stall", 0.0)
        assert NULL_TRACER.flight_dumps() == []
        assert NULL_TRACER.watchdog_counts() == {}
        assert NULL_TRACER.worker_counts() == {}

    def test_watchdog_counts_survive_ring_wrap(self):
        tr = Tracer(max_events=4)
        for _ in range(10):
            tr.record_watchdog("el", "stall", 0.0)
        tr.record_watchdog("el", "queue", 0.0)
        assert tr.watchdog_counts() == {"el": {"stall": 10, "queue": 1}}

    def test_record_flight_instant_event(self):
        tr = Tracer()
        tr.record_flight("slo_breach", 1.0, path="/p")
        assert tr.flight_dumps() == [("slo_breach", 1.0,
                                      {"path": "/p"})]


# -- CLI ---------------------------------------------------------------------

class TestFlightCLI:
    def test_flight_list_and_inspect(self, tmp_path, capsys):
        from nnstreamer_tpu.__main__ import main

        rec = FlightRecorder(str(tmp_path))
        rec.trigger("manual", {"why": "cli"})
        assert main(["flight", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "flight-0001-manual" in out and "manual" in out
        assert main(["flight", str(tmp_path), "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert rows[0]["kind"] == "manual"
        assert main(["flight", str(tmp_path),
                     "--inspect", "flight-0001-manual"]) == 0
        b = json.loads(capsys.readouterr().out)
        assert b["cause"]["cause"] == {"why": "cli"}

    def test_flight_empty_dir_exits_nonzero(self, tmp_path, capsys):
        from nnstreamer_tpu.__main__ import main

        assert main(["flight", str(tmp_path)]) == 1
