"""Metrics export plane (serving/metrics.py): snapshot flattening,
Prometheus text exposition (parsed line-by-line), the stdlib HTTP
endpoint, and the scrape-monotonicity contract — two consecutive
scrapes under load never see a counter or histogram bucket decrease."""

import re
import time

import numpy as np
import pytest

from nnstreamer_tpu.runtime.tracing import Tracer
from nnstreamer_tpu.serving.metrics import (
    MetricsServer, escape_label_value, metrics_snapshot,
    parse_prometheus, render_prometheus, scrape, top_table)
from nnstreamer_tpu.tensor.buffer import TensorBuffer


def _admission(offered=10, admitted=8, replied=7, depth=1, inflight=0):
    return {"offered": offered, "admitted": admitted, "replied": replied,
            "rejected": {"queue_full": offered - admitted},
            "shed": {"expired": admitted - replied - depth - inflight},
            "depth": depth, "inflight": inflight, "depth_peak": 4,
            "max_pending": 8, "max_inflight": 0,
            "shed_policy": "reject-newest"}


def _pool(replied=(4, 3)):
    return {"pool": {"workers": len(replied), "live": len(replied),
                     "ready": len(replied), "degraded": 0, "restarts": 1,
                     "kills": 0, "reoffered": 2, "pending": 0,
                     "epoch": 0},
            "workers": [{"wid": i, "pid": 100 + i, "state": "ready",
                         "inflight": 0, "hb_age_ms": 1.0, "restarts": i,
                         "kills": 0, "replied": r}
                        for i, r in enumerate(replied)]}


def _mesh(replied=(9, 31)):
    """router.stats()-shaped snapshot: per-host replied sums to the
    admission plane's replied — the cross-host conservation check."""
    return {
        "mesh": {"hosts": len(replied), "ready": len(replied) - 1,
                 "fenced": 1, "epoch": 2, "reoffered": 3,
                 "busy_reroutes": 1, "stale_results": 0, "pending": 0,
                 "lease_s": 1.0},
        "hosts": [
            {"host": f"host{i}", "state": "READY" if i else "FENCED",
             "zone": "", "capacity_rps": 100.0, "outstanding": i,
             "replied": r, "busies": i, "lease_age_ms": 12.5,
             "fence_cause": None if i else "lease_expired",
             "versions": {},
             "remote": {"offered": r + 1, "admitted": r,
                        "replied": r - 1}}
            for i, r in enumerate(replied)],
        "admission": _admission(offered=41, admitted=40,
                                replied=sum(replied), depth=0,
                                inflight=0),
    }


def _traced(n=5, name="echo"):
    tr = Tracer()
    buf = TensorBuffer.of(np.ones((2,), np.float32))
    t0 = time.perf_counter()
    for i in range(n):
        tr.record_process(name, buf, t0, t0 + 1e-4 * (i + 1))
    return tr


class TestExposition:
    def test_type_and_help_line_per_family(self):
        text = render_prometheus(metrics_snapshot(
            tracer=_traced(), admission=_admission(), pool=_pool()))
        parsed = parse_prometheus(text)
        for fam in ("nns_admission_offered_total",
                    "nns_admission_rejected_total",
                    "nns_admission_depth",
                    "nns_pool_restarts_total",
                    "nns_worker_replied_total",
                    "nns_element_proctime_seconds",
                    "nns_trace_events_total"):
            assert fam in parsed, f"family {fam} missing"
            assert parsed[fam].get("type"), f"no TYPE line for {fam}"
            assert parsed[fam].get("help"), f"no HELP line for {fam}"
        # _total families are counters; bare gauges are gauges
        assert parsed["nns_admission_offered_total"]["type"] == "counter"
        assert parsed["nns_admission_depth"]["type"] == "gauge"
        assert parsed["nns_element_proctime_seconds"]["type"] \
            == "histogram"
        # every non-comment line is "name{labels} value" — no stray
        # formats a scraper would reject
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            assert re.match(
                r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$', line), \
                f"malformed exposition line: {line!r}"

    def test_label_escaping_round_trips(self):
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        tr = _traced(2, name='we"ird\\el\nem')
        text = render_prometheus(metrics_snapshot(tracer=tr))
        # raw newline inside a quoted label value would break
        # line-oriented parsers
        for line in text.splitlines():
            assert "\r" not in line
        assert '\\"ird' in text and "\\\\el" in text and "\\nem" in text

    def test_histogram_buckets_cumulative_with_inf(self):
        tr = _traced(4)
        text = render_prometheus(metrics_snapshot(tracer=tr))
        fam = parse_prometheus(text)["nns_element_proctime_seconds"]
        buckets = sorted(
            (float("inf") if 'le="+Inf"' in k else
             float(re.search(r'le="([^"]+)"', k).group(1)), v)
            for k, v in fam["samples"].items() if "_bucket{" in k)
        vals = [v for _, v in buckets]
        assert vals == sorted(vals)          # cumulative ⇒ monotone
        assert buckets[-1][0] == float("inf")
        assert vals[-1] == 4                 # +Inf bucket == _count
        count = [v for k, v in fam["samples"].items()
                 if k.endswith("_count}") or "_count{" in k]
        assert count == [4]

    def test_counter_families_never_negative(self):
        series = metrics_snapshot(admission=_admission(), pool=_pool())
        for s in series:
            if s["type"] == "counter":
                for _, v in s["samples"]:
                    assert v >= 0, s["name"]

    def test_parse_handles_bucket_sum_count_suffixes(self):
        tr = _traced(3)
        parsed = parse_prometheus(render_prometheus(
            metrics_snapshot(tracer=tr)))
        fam = parsed["nns_element_proctime_seconds"]
        # suffixed sample lines are attributed to the base family, not
        # invented as families of their own
        assert "nns_element_proctime_seconds_bucket" not in parsed
        assert any("_sum{" in k or k.endswith("_sum}")
                   for k in fam["samples"])


class TestMetricsServer:
    def test_scrapes_are_monotone_under_load(self):
        tr = _traced(2)
        state = {"offered": 10}

        def collect():
            return metrics_snapshot(
                tracer=tr, admission=_admission(state["offered"]),
                pool=_pool())

        srv = MetricsServer(collect)
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            p1 = parse_prometheus(scrape(url))
            # the plane keeps counting between scrapes
            state["offered"] += 7
            buf = TensorBuffer.of(np.ones((2,), np.float32))
            t0 = time.perf_counter()
            for _ in range(3):
                tr.record_process("echo", buf, t0, t0 + 2e-4)
            p2 = parse_prometheus(scrape(url))
            for fam, info in p1.items():
                if info.get("type") not in ("counter", "histogram"):
                    continue
                for k, v in info["samples"].items():
                    v2 = p2[fam]["samples"].get(k)
                    assert v2 is not None and v2 >= v, (fam, k, v, v2)
            # and actually increased where we counted
            assert p2["nns_admission_offered_total"]["samples"][
                "nns_admission_offered_total"] == 17.0
        finally:
            srv.close()

    def test_healthz_and_unknown_path(self):
        import json
        import urllib.error
        import urllib.request

        srv = MetricsServer(lambda: [],
                            health=lambda: {"workers": 2})
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{base}/healthz",
                                        timeout=5) as r:
                info = json.loads(r.read().decode())
            assert info["ok"] and info["workers"] == 2
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            srv.close()

    def test_collect_failure_yields_503_not_crash(self):
        import urllib.error
        import urllib.request

        calls = {"n": 0}

        def collect():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return metrics_snapshot(admission=_admission())

        srv = MetricsServer(collect)
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url, timeout=5)
            assert ei.value.code == 503
            # endpoint survives and serves the next scrape
            assert "nns_admission_offered_total" in scrape(url)
        finally:
            srv.close()

    def test_content_type_is_exposition_format(self):
        import urllib.request

        srv = MetricsServer(lambda: metrics_snapshot(
            admission=_admission()))
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=5) as r:
                ctype = r.headers["Content-Type"]
            assert ctype.startswith("text/plain")
            assert "version=0.0.4" in ctype
        finally:
            srv.close()


class TestMeshExposition:
    def test_host_labels_round_trip(self):
        """ISSUE 12 satellite: per-host series survive the full
        render → parse cycle with their host labels intact, and the
        per-host goodput sums to the admission plane's replied — the
        cross-host conservation check, as scraped."""
        snap = _mesh(replied=(9, 31))
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            admission=snap["admission"], mesh=snap)))
        rep = parsed["nns_host_replied_total"]["samples"]
        by_host = {re.search(r'host="([^"]+)"', k).group(1): v
                   for k, v in rep.items()}
        assert by_host == {"host0": 9.0, "host1": 31.0}
        adm = parsed["nns_admission_replied_total"]["samples"][
            "nns_admission_replied_total"]
        assert sum(by_host.values()) == adm == 40.0
        # mesh-level counters/gauges made it through too
        assert parsed["nns_mesh_reoffered_total"]["samples"][
            "nns_mesh_reoffered_total"] == 3.0
        assert parsed["nns_mesh_fenced"]["samples"][
            "nns_mesh_fenced"] == 1.0
        # up gauge keys on host AND state so a flap is visible as a
        # label change, not a silent value swap
        up = parsed["nns_host_up"]["samples"]
        fenced = [k for k, v in up.items() if v == 0.0]
        assert len(fenced) == 1
        assert 'host="host0"' in fenced[0]
        assert 'state="FENCED"' in fenced[0]

    def test_lease_carried_remote_counters_exported(self):
        snap = _mesh(replied=(9, 31))
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            mesh=snap)))
        for key in ("offered", "admitted", "replied"):
            fam = parsed[f"nns_host_local_{key}_total"]
            assert fam["type"] == "counter"
            assert len(fam["samples"]) == 2
        local = parsed["nns_host_local_replied_total"]["samples"]
        assert sum(local.values()) == (9 - 1) + (31 - 1)


class TestLoopAndShmExposition:
    def test_shm_transport_counters_round_trip(self):
        """ISSUE 20 satellite: the pool's shm-lane counters survive
        render → parse, typed as counters, and the lane split
        (shm_frames vs shm_fallbacks) is visible from one scrape."""
        pool = _pool()
        pool["pool"].update(shm_frames=80, shm_bytes=5_242_880,
                            shm_fallbacks=2)
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            pool=pool)))
        for fam, want in (("nns_shm_frames_total", 80.0),
                          ("nns_shm_bytes_total", 5242880.0),
                          ("nns_shm_fallbacks_total", 2.0)):
            assert parsed[fam]["type"] == "counter"
            assert parsed[fam].get("help")
            assert parsed[fam]["samples"][fam] == want

    def test_pipe_only_pool_still_exports_zeroed_lane(self):
        # a pool that never used shm still exposes the families at 0 —
        # dashboards don't need existence checks
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            pool=_pool())))
        assert parsed["nns_shm_frames_total"]["samples"][
            "nns_shm_frames_total"] == 0.0

    def test_compiled_loop_counters_round_trip(self):
        """Windows entered / frames windowed / bails-by-cause as
        recorded by the scheduler's tracer hooks, scraped back."""
        tr = _traced(6, name="f")
        t0 = time.perf_counter()
        tr.record_compiled_window("f", 4, t0, t0 + 1e-3)
        tr.record_compiled_window("f", 2, t0, t0 + 2e-3)
        tr.record_loop_bail("f", "eos", t0)
        tr.record_loop_bail("f", "shape", t0)
        tr.record_loop_bail("f", "shape", t0)
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            tracer=tr)))
        assert parsed["nns_loop_entries_total"]["samples"][
            'nns_loop_entries_total{element="f"}'] == 2.0
        assert parsed["nns_compiled_steps_total"]["samples"][
            'nns_compiled_steps_total{element="f"}'] == 6.0
        fam = parsed["nns_loop_bails_total"]
        assert fam["type"] == "counter"
        by_cause = {re.search(r'cause="([^"]+)"', k).group(1): v
                    for k, v in fam["samples"].items()}
        assert by_cause == {"eos": 1.0, "shape": 2.0}


class TestLLMRowExposition:
    """ISSUE 37: the engine's account of every decode launch's rows, as
    `extra_stats()` carries it, is one counter family by state."""

    @staticmethod
    def _llm(decode=620, unfed=100):
        return {"llm": {
            "tokens_out": 700, "finished": 9, "prefilling": 2,
            "chunk_deferred_steps": 33,
            "rows": {"decode": decode, "prefilling": 240, "retiring": 1,
                     "blocked": 30, "blocked_state": 9, "unfed": unfed,
                     "other": 0, "total": decode + unfed + 280},
            "executor": {"kernel_invokes": {"xla": 125}, "paged_kernel":
                         "xla", "chunk_prefills": 11}}}

    def test_row_steps_round_trip_by_state(self):
        llm = self._llm()
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=llm)))
        fam = parsed["nns_llm_row_steps_total"]
        assert fam["type"] == "counter" and fam.get("help")
        by_state = {re.search(r'state="([^"]+)"', k).group(1): v
                    for k, v in fam["samples"].items()}
        rows = llm["llm"]["rows"]
        assert by_state == {k: float(v) for k, v in rows.items()
                            if k != "total"}
        assert all('element="llm"' in k for k in fam["samples"])
        # the states are the whole of it: no series for the total
        assert sum(by_state.values()) == rows["total"]
        held = parsed["nns_llm_chunk_deferred_steps_total"]
        assert held["type"] == "counter"
        assert held["samples"][
            'nns_llm_chunk_deferred_steps_total{element="llm"}'] == 33.0

    def test_an_engine_without_the_account_exports_the_family_empty(self):
        old = self._llm()
        del old["llm"]["rows"], old["llm"]["chunk_deferred_steps"]
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=old)))
        assert list(parsed["nns_llm_row_steps_total"]["samples"].values()
                    ) == [0.0]
        assert parsed["nns_llm_chunk_deferred_steps_total"]["samples"][
            'nns_llm_chunk_deferred_steps_total{element="llm"}'] == 0.0

    def test_the_pools_grants_round_trip(self):
        """ISSUE 38: growth grants as a counter, the largest admitted
        peak and the most blocks live as gauges; an engine without them
        exports zeros."""
        llm = self._llm()
        llm["llm"]["cache"] = {"blocks_grown": 3911, "admit_peak_blocks":
                               612, "blocks_live_high_water": 607}
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=llm)))
        for fam, typ, want in (
                ("nns_llm_blocks_grown_total", "counter", 3911.0),
                ("nns_llm_admit_peak_blocks", "gauge", 612.0),
                ("nns_llm_blocks_live_high_water", "gauge", 607.0)):
            assert parsed[fam]["type"] == typ and parsed[fam].get("help")
            assert parsed[fam]["samples"] == {fam + '{element="llm"}': want}
        old = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=self._llm())))
        assert old["nns_llm_blocks_grown_total"]["samples"] == {
            'nns_llm_blocks_grown_total{element="llm"}': 0.0}

    def test_the_top_view_rates_the_states(self):
        prev = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=self._llm())))
        cur = parse_prometheus(render_prometheus(metrics_snapshot(
            llm=self._llm(decode=1240, unfed=120))))
        lines = top_table(prev, cur, 2.0)
        mine = [ln for ln in lines if "nns_llm_row_steps_total" in ln]
        assert len(mine) == 7
        decode = next(ln for ln in mine if 'state="decode"' in ln)
        assert decode.split()[-1] == "310.0"


def _sharded_replicas(invokes=(6, 4), fenced=None):
    """Synthetic ShardedReplicaSet.stats() — the shape placement's
    ReplicaSet emits plus the shard-group keys sharding.py adds."""
    rows = []
    for g, inv in enumerate(invokes):
        state = "fenced" if g == fenced else "ready"
        rows.append({"device": g * 2, "platform": "cpu",
                     "invokes": inv, "batches": inv, "errors": 0,
                     "queue_depth": 0, "up": state == "ready",
                     "state": state, "compile_count": 1,
                     "adopted_epoch": 1,
                     "group": g, "devices": [g * 2, g * 2 + 1],
                     "shards": 2})
    return {"replicas": rows, "devices": len(invokes),
            "live": sum(1 for r in rows if r["up"]),
            "routed": sum(invokes), "reoffers": 0, "rejected": 0,
            "fences": 1 if fenced is not None else 0,
            "group_size": 2,
            "leases": {"free": 8 - 2 * len(invokes),
                       "leased": 2 * len(invokes), "fenced": 0}}


class TestShardExposition:
    def test_shard_family_round_trips_and_conserves(self):
        """Sharded-serving satellite: nns_shard_* series survive
        render → parse with group/devices labels intact, and Σ shard
        group invokes == the filter's replica invokes — tensor-parallel
        conservation from one scrape."""
        st = _sharded_replicas(invokes=(6, 4))
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st})))
        fam = parsed["nns_shard_group_invokes_total"]
        assert fam["type"] == "counter"
        by_group = {re.search(r'group="([^"]+)"', k).group(1): v
                    for k, v in fam["samples"].items()}
        assert by_group == {"0": 6.0, "1": 4.0}
        # the per-chip replica family carries the same rows, so the
        # shard sum equals the replica sum equals filter invokes
        rep = parsed["nns_replica_invokes_total"]["samples"]
        assert sum(by_group.values()) == sum(rep.values()) == 10.0
        # devices label names every member chip of the group
        assert any('devices="0,1"' in k for k in fam["samples"])
        # width + lease ledger exported as gauges
        assert parsed["nns_shard_group_size"]["samples"][
            'nns_shard_group_size{filter="f"}'] == 2.0
        leases = parsed["nns_shard_leased_chips"]["samples"]
        assert leases['nns_shard_leased_chips{filter="f",'
                      'state="leased"}'] == 4.0
        # adopted epoch: one distinct value across groups == atomic swap
        epochs = set(parsed["nns_shard_group_adopted_epoch"]
                     ["samples"].values())
        assert epochs == {1.0}

    def test_member_fence_shows_as_group_down(self):
        st = _sharded_replicas(invokes=(6, 4), fenced=1)
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st})))
        up = parsed["nns_shard_group_up"]["samples"]
        down = [k for k, v in up.items() if v == 0.0]
        assert len(down) == 1
        assert 'group="1"' in down[0] and 'state="fenced"' in down[0]

    def test_unsharded_stats_emit_no_shard_family(self):
        st = _sharded_replicas(invokes=(3,))
        for r in st["replicas"]:
            for k in ("group", "devices", "shards"):
                r.pop(k)
        st.pop("group_size"); st.pop("leases")
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st})))
        assert "nns_replica_invokes_total" in parsed
        assert not any(f.startswith("nns_shard_") for f in parsed)

    def test_shard_rows_appear_in_top_table(self):
        cur = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": _sharded_replicas()})))
        lines = "\n".join(top_table({}, cur, 1.0))
        assert "nns_shard_group_invokes_total" in lines
        assert "nns_shard_group_up" in lines


class TestTopView:
    def test_counter_rates_and_gauges(self):
        p1 = parse_prometheus(render_prometheus(metrics_snapshot(
            admission=_admission(offered=100))))
        p2 = parse_prometheus(render_prometheus(metrics_snapshot(
            admission=_admission(offered=150))))
        lines = "\n".join(top_table(p1, p2, dt_s=2.0))
        # 50 more offered over 2s → 25.0/s
        m = re.search(r"nns_admission_offered_total\s+150\s+25\.0",
                      lines)
        assert m, lines
        assert "nns_admission_depth" in lines

    def test_histogram_families_stay_out_of_table(self):
        cur = parse_prometheus(render_prometheus(metrics_snapshot(
            tracer=_traced())))
        lines = "\n".join(top_table({}, cur, 1.0))
        assert "proctime_seconds_bucket" not in lines
