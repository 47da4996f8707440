"""Multi-chip device placement (serving/placement.py, ISSUE 14).

Covers the three placement surfaces on the 8-device emulated host mesh
(marker `multichip`, fixture `eight_cpu_devices`):

- data-parallel replicas: bit-parity with the single-device path at
  devices=1/2/4/8, least-outstanding spread, exact invoke conservation
  across a chaos fence, store-integrated epoch-atomic hot swap with
  zero post-flip recompiles, and the tensor_filter `devices=` property
  (routing, stats, soft declines);
- profiled segmentation: the linear-partition DP, tracer-profiled
  plans, plan-aware fuse_segments cuts, and end-to-end parity of a
  segmented pipeline vs the unsegmented one;
- chip leases: the supervisor-side ChipLeaseTable (fence + re-lease
  preference), WorkerPool chip partitioning across slots, and the
  ScalingController's chip-weighted capacity math;

plus the metrics plane: replica/segment series survive render → parse
with Σ per-chip invokes equal to the filter's invoke count.
"""

import itertools
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu import PipelineRunner, TensorBuffer, parse_launch
from nnstreamer_tpu.backends.xla import ModelBundle
from nnstreamer_tpu.core.errors import BackendError, StreamError
from nnstreamer_tpu.edge.query import QueryServer
from nnstreamer_tpu.graph.optimize import fuse_segments
from nnstreamer_tpu.serving import compile_cache
from nnstreamer_tpu.serving.metrics import (
    metrics_snapshot, parse_prometheus, render_prometheus, top_table)
from nnstreamer_tpu.serving.placement import (
    ChipLeaseTable, ReplicaSet, accelerator_for, apply_plan, device_of,
    plan_from_tracer, segment_plan, visible_devices)
from nnstreamer_tpu.serving.pool import PooledQueryServer, WorkerPool
from nnstreamer_tpu.serving.store import reset_store
from nnstreamer_tpu.serving.tenancy import ScalingController, TenantTable
from nnstreamer_tpu.serving.worker import WorkerSpec

pytestmark = pytest.mark.multichip

_sid = itertools.count(9000)


@pytest.fixture(autouse=True)
def _fresh_store():
    store = reset_store()
    compile_cache.reset()
    yield store
    reset_store()
    compile_cache.reset()
    QueryServer.reset_all()


def _bundle(seed=3, dim=16):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(dim, dim)).astype(np.float32)

    def fn(params, x):
        return (x @ params["w"],)

    return ModelBundle(fn=fn, params={"w": w}, name="plc_mlp"), dim


def _open(n, bundle, **kw):
    return ReplicaSet.open("xla", {"model": bundle, "custom": ""}, n,
                           name=f"rs{n}", **kw)


# -- device enumeration -------------------------------------------------------

class TestDevices:
    def test_emulated_mesh_visible(self, eight_cpu_devices):
        assert len(visible_devices()) >= 8

    def test_accelerator_for_pins_platform_and_ordinal(
            self, eight_cpu_devices):
        assert accelerator_for(3) == f"{device_of(3).platform}:3"

    def test_out_of_range_is_typed(self, eight_cpu_devices):
        with pytest.raises(BackendError, match="out of range"):
            device_of(10_000)


# -- data-parallel replicas ---------------------------------------------------

class TestReplicaSet:
    def test_bit_parity_across_device_counts(self, eight_cpu_devices):
        """The acceptance check: devices=1/2/4/8 produce bit-identical
        outputs — each replica IS the single-device program, placed
        elsewhere."""
        bundle, dim = _bundle()
        x = np.linspace(-1, 1, 4 * dim,
                        dtype=np.float32).reshape(4, dim)
        ref = None
        for n in (1, 2, 4, 8):
            rs = _open(n, bundle)
            try:
                outs = [rs.invoke((x,)) for _ in range(2 * n)]
            finally:
                rs.close()
            if ref is None:
                ref = np.asarray(outs[0][0])
            for o in outs:
                np.testing.assert_array_equal(np.asarray(o[0]), ref)

    def test_round_robin_spreads_idle_load(self, eight_cpu_devices):
        bundle, dim = _bundle()
        x = np.ones((1, dim), np.float32)
        rs = _open(4, bundle)
        try:
            for _ in range(12):
                rs.invoke((x,))
            st = rs.stats()
        finally:
            rs.close()
        assert [r["invokes"] for r in st["replicas"]] == [3, 3, 3, 3]
        assert st["routed"] == 12 and st["live"] == 4

    def test_fence_conserves_invokes_exactly(self, eight_cpu_devices):
        """Σ replica invokes == frames served, exactly, through a chip
        loss — the fenced replica stops, survivors absorb the rest."""
        bundle, dim = _bundle()
        x = np.ones((1, dim), np.float32)
        rs = _open(4, bundle)
        try:
            for _ in range(4):
                rs.invoke((x,))
            assert rs.fence(0, "test chaos")
            assert not rs.fence(0, "twice")   # idempotent
            for _ in range(6):
                rs.invoke((x,))
            st = rs.stats()
        finally:
            rs.close()
        assert sum(r["invokes"] for r in st["replicas"]) == 10
        assert st["live"] == 3 and rs.live_replicas() == 3
        dead = next(r for r in st["replicas"] if r["device"] == 0)
        assert dead["state"] == "fenced" and not dead["up"]
        # nothing routed to the fenced chip after the fence
        assert dead["invokes"] == 1

    def test_all_fenced_rejects_typed(self, eight_cpu_devices):
        bundle, dim = _bundle()
        rs = _open(2, bundle)
        try:
            rs.fence(0)
            rs.fence(1)
            fut = rs.submit((np.ones((1, dim), np.float32),))
            with pytest.raises(StreamError, match="no live replica"):
                fut.result(5.0)
            assert rs.stats()["rejected"] == 1
        finally:
            rs.close()

    def test_too_many_devices_is_typed(self, eight_cpu_devices):
        bundle, _ = _bundle()
        with pytest.raises(BackendError, match="only"):
            _open(len(visible_devices()) + 1, bundle)

    def test_swap_requires_store_backing(self, eight_cpu_devices):
        bundle, _ = _bundle()
        rs = _open(2, bundle)
        try:
            with pytest.raises(BackendError, match="store"):
                rs.swap()
        finally:
            rs.close()


class TestReplicaHotSwap:
    def test_epoch_atomic_swap_zero_postflip_recompiles(
            self, eight_cpu_devices, _fresh_store):
        """The acceptance check: after one store update every replica
        serves the new version in the SAME epoch, and the flip costs
        zero compiles — prepare pre-warmed the exact jits on every
        chip before anything moved."""
        _fresh_store.register("plc_m", lambda x: (x * 2.0,))
        _fresh_store.register("plc_m", lambda x: (x + 100.0,))  # v2
        x = np.full((4,), 3.0, np.float32)
        rs = ReplicaSet.open("xla", {"model": "store://plc_m",
                                     "custom": ""}, 4, name="swap4")
        try:
            for _ in range(8):                # warm every replica
                (out,) = rs.invoke((x,))
            np.testing.assert_allclose(np.asarray(out), x * 2.0)
            assert len(set(rs.adopted_epochs())) == 1
            rep = rs.swap(2)
            assert rep["to_version"] == 2
            assert rep["handles"] == 4       # every chip attached
            counts_at_flip = rs.compile_counts()
            outs = [rs.invoke((x,)) for _ in range(8)]
            for (o,) in outs:
                np.testing.assert_allclose(np.asarray(o), x + 100.0)
            # all four chips landed in the same epoch, with no compile
            # after the flip (prewarm staged them)
            assert len(set(rs.adopted_epochs())) == 1
            assert rs.compile_counts() == counts_at_flip
        finally:
            rs.close()


class TestFilterDevicesProp:
    def _pipe(self, store, devices, name="f"):
        store.register("plc_p", lambda x: (x * 2.0 + 1.0,))
        return parse_launch(
            f"appsrc name=src dims=4 types=float32 ! "
            f"tensor_filter name={name} model=store://plc_p "
            f"devices={devices} ! tensor_sink name=out")

    def _run(self, pipe, frames=12):
        runner = PipelineRunner(pipe, trace=True)
        runner.start()
        src, sink = pipe.get("src"), pipe.get("out")
        try:
            for i in range(frames):
                src.push(TensorBuffer.of(
                    np.full((4,), float(i), np.float32), pts=i))
            src.end()
            runner.wait(30)
        finally:
            runner.stop()
        return ({int(b.pts): np.asarray(b.tensors[0])
                 for b in sink.results}, runner)

    def test_pipeline_parity_and_conservation(
            self, eight_cpu_devices, _fresh_store):
        base, _ = self._run(self._pipe(_fresh_store, devices=0))
        store = reset_store()
        pipe = self._pipe(store, devices=4)
        rep, _ = self._run(pipe)
        assert rep.keys() == base.keys()
        for pts, ref in base.items():
            np.testing.assert_array_equal(rep[pts], ref)
        st = pipe.get("f").extra_stats()
        assert st["replica_devices"] == 4 and st["replica_live"] == 4
        assert st["replica_invokes"] == 12
        assert sum(r["invokes"] for r in st["replicas"]) == 12

    def test_fence_mid_stream_conserves(self, eight_cpu_devices,
                                        _fresh_store):
        """Σ replica replied == filter replied, exactly, across a
        chaos fence injected mid-stream at the pipeline level."""
        pipe = self._pipe(_fresh_store, devices=2)
        runner = PipelineRunner(pipe)
        runner.start()
        src, sink = pipe.get("src"), pipe.get("out")
        f = pipe.get("f")
        try:
            for i in range(6):
                src.push(TensorBuffer.of(
                    np.full((4,), float(i), np.float32), pts=i))
            while len(sink.results) < 6:
                time.sleep(0.005)
            assert f.replicas.fence(0, "test chaos")
            for i in range(6, 12):
                src.push(TensorBuffer.of(
                    np.full((4,), float(i), np.float32), pts=i))
            src.end()
            runner.wait(30)
        finally:
            runner.stop()
        assert len(sink.results) == 12
        st = f.extra_stats()
        assert st["replica_invokes"] == 12      # exact, no dupes/loss
        assert st["replica_live"] == 1 and st["replica_fences"] == 1

    def test_explicit_accelerator_declines_softly(
            self, eight_cpu_devices, _fresh_store):
        _fresh_store.register("plc_p", lambda x: (x * 2.0 + 1.0,))
        pipe = parse_launch(
            "appsrc name=src dims=4 types=float32 ! "
            "tensor_filter name=f model=store://plc_p devices=2 "
            "accelerator=cpu:0 ! tensor_sink name=out")
        rep, _ = self._run(pipe)
        assert len(rep) == 12                    # single-device served
        f = pipe.get("f")
        assert f.replicas is None
        assert "accelerator" in f.extra_stats()["replica_decline"]

    def test_canary_split_declines_softly(self, eight_cpu_devices,
                                          _fresh_store):
        _fresh_store.register("plc_c", lambda x: (x * 2.0,))
        _fresh_store.register("plc_c", lambda x: (x * 3.0,))
        pipe = parse_launch(
            "appsrc name=src dims=4 types=float32 ! "
            "tensor_filter name=f model=store://plc_c@2:0.5 devices=2 "
            "! tensor_sink name=out")
        rep, _ = self._run(pipe)
        assert len(rep) == 12
        f = pipe.get("f")
        assert f.replicas is None
        assert "canary" in f.extra_stats()["replica_decline"]


# -- profiled segmentation ----------------------------------------------------

class TestSegmentPlanDP:
    def test_balanced_cut_minimizes_bottleneck(self):
        plan = segment_plan(
            [("a", 1.0), ("b", 3.0), ("c", 1.0), ("d", 1.0)], 2)
        assert plan.stages == [["a", "b"], ["c", "d"]]
        assert plan.devices == [0, 1]
        assert plan.stage_times_s == [4.0, 2.0]
        assert plan.bubble_fraction == pytest.approx(0.25)
        assert plan.total_s == pytest.approx(6.0)

    def test_dominant_element_prefers_fewest_stages(self):
        # the bottleneck is element a no matter how many cuts; extra
        # cuts buy nothing but handoffs, so the plan stays at 2 stages
        plan = segment_plan(
            [("a", 10.0), ("b", 0.1), ("c", 0.1)], 3)
        assert len(plan.stages) == 2
        assert plan.stages[0] == ["a"]

    def test_more_elements_than_devices(self):
        plan = segment_plan(
            [(f"e{i}", 1.0) for i in range(6)], 2)
        assert len(plan.stages) == 2
        assert sorted(n for g in plan.stages for n in g) == \
            sorted(f"e{i}" for i in range(6))

    def test_zero_profile_collapses_to_one_stage(self):
        plan = segment_plan([("a", 0.0), ("b", 0.0)], 4)
        assert plan.stages == [["a", "b"]]
        assert plan.bubble_fraction == 0.0

    def test_empty_profile_is_typed(self):
        with pytest.raises(BackendError, match="empty"):
            segment_plan([], 2)

    def test_stage_of_and_report_shape(self):
        plan = segment_plan([("a", 2.0), ("b", 2.0)], 2)
        assert plan.stage_of() == {"a": 0, "b": 1}
        rep = plan.report()
        assert rep["bottleneck_s"] == 2.0
        assert [r["elements"] for r in rep["stages"]] == [["a"], ["b"]]


def _three_filter_pipe(store):
    store.register("plc_s1", lambda x: (x * 2.0,))
    store.register("plc_s2", lambda x: (x + 1.0,))
    store.register("plc_s3", lambda x: (-x,))
    return parse_launch(
        "appsrc name=src dims=4 types=float32 ! "
        "tensor_filter name=s1 model=store://plc_s1 ! "
        "tensor_filter name=s2 model=store://plc_s2 ! "
        "tensor_filter name=s3 model=store://plc_s3 ! "
        "tensor_sink name=out")


def _push_and_collect(pipe, frames=10, **runner_kw):
    runner = PipelineRunner(pipe, **runner_kw)
    runner.start()
    src, sink = pipe.get("src"), pipe.get("out")
    try:
        for i in range(frames):
            src.push(TensorBuffer.of(
                np.full((4,), float(i), np.float32), pts=i))
        src.end()
        runner.wait(30)
    finally:
        runner.stop()
    return ({int(b.pts): np.asarray(b.tensors[0])
             for b in sink.results}, runner)


class TestSegmentedPipeline:
    def test_profiled_plan_and_parity(self, eight_cpu_devices,
                                      _fresh_store):
        """The acceptance check: trace → plan → apply → rerun matches
        the unsegmented pipeline within 1e-6, with each stage pinned to
        its own device."""
        base, runner = _push_and_collect(
            _three_filter_pipe(_fresh_store), trace=True,
            device_segments=False)
        plan = plan_from_tracer(runner.tracer, ["s1", "s2", "s3"], 4)
        assert plan.source == "tracer"
        assert sum(len(g) for g in plan.stages) == 3
        store = reset_store()
        pipe = _three_filter_pipe(store)
        pinned = apply_plan(pipe, plan)
        assert pinned == 3
        assert pipe.segment_plan is plan
        # each planned stage landed on its own device ordinal
        accels = {pipe.get(g[0]).props["accelerator"]
                  for g in plan.stages}
        assert len(accels) == len(plan.stages)
        seg, _ = _push_and_collect(pipe)
        assert seg.keys() == base.keys()
        for pts, ref in base.items():
            assert float(np.max(np.abs(seg[pts] - ref))) <= 1e-6

    def test_fuse_segments_respects_plan_cut(self, eight_cpu_devices,
                                             _fresh_store):
        pipe = _three_filter_pipe(_fresh_store)
        plan = segment_plan(
            [("s1", 1.0), ("s2", 1.0), ("s3", 1.0)], 3)
        apply_plan(pipe, plan)
        # every adjacent pair sits across a cut: nothing may fuse
        assert fuse_segments(pipe) == 0
        assert set(pipe.elements) >= {"s1", "s2", "s3"}

    def test_fuse_segments_fuses_within_stage(self, eight_cpu_devices,
                                              _fresh_store):
        pipe = _three_filter_pipe(_fresh_store)
        plan = segment_plan(
            [("s1", 1.0), ("s2", 1.0), ("s3", 4.0)], 2)
        assert plan.stages == [["s1", "s2"], ["s3"]]
        apply_plan(pipe, plan)
        # s1+s2 share a stage and fuse; the s2|s3 cut holds
        assert fuse_segments(pipe) == 1
        assert "s3" in pipe.elements and "s2" not in pipe.elements

    def test_measured_report_reads_live_profile(self, eight_cpu_devices,
                                                _fresh_store):
        base, runner = _push_and_collect(
            _three_filter_pipe(_fresh_store), trace=True,
            device_segments=False)
        plan = plan_from_tracer(runner.tracer, ["s1", "s2", "s3"], 3)
        rep = plan.measured_report(runner.tracer)
        assert all(r["measured_s"] > 0 for r in rep["stages"])
        assert 0.0 <= rep["measured_bubble_fraction"] < 1.0


# -- chip leases --------------------------------------------------------------

class TestChipLeaseTable:
    def test_lease_fence_release_prefers_own_chips(self):
        t = ChipLeaseTable(range(8))
        a = t.lease("w0", 4)
        b = t.lease("w1", 4)
        assert a == (0, 1, 2, 3) and b == (4, 5, 6, 7)
        assert t.fence("w0") == (0, 1, 2, 3)
        assert t.snapshot()["counts"] == {"free": 0, "leased": 4,
                                          "fenced": 4}
        # the restarted owner gets its own chips back, not w1's
        assert t.lease("w0", 4) == (0, 1, 2, 3)
        assert t.snapshot()["counts"]["leased"] == 8
        assert t.snapshot()["fences_total"] == 4

    def test_shortfall_is_typed_not_silent(self):
        t = ChipLeaseTable(range(4))
        t.lease("w0", 3)
        with pytest.raises(BackendError, match="wanted 2"):
            t.lease("w1", 2)
        # the failed lease took nothing
        assert t.snapshot()["counts"]["free"] == 1

    def test_release_returns_chips_to_pool(self):
        t = ChipLeaseTable(range(4))
        t.lease("w0", 4)
        t.fence("w0")
        assert t.release("w0") == (0, 1, 2, 3)
        assert t.chips_of("w0") == ()
        # a different owner can lease them now
        assert t.lease("w1", 4) == (0, 1, 2, 3)
        assert t.snapshot()["releases_total"] == 4


class TestPoolChips:
    def test_chips_must_divide_evenly(self):
        with pytest.raises(ValueError, match="divide"):
            WorkerPool(QueryServer.get(next(_sid)),
                       WorkerSpec(kind="echo"), 2, chips=[0, 1, 2])

    def test_more_device_workers_than_chips_refused(self, monkeypatch):
        """A chip belongs to one process at a time: a pipeline pool with
        more workers than leased chips is a typed error at construction,
        not N processes asking for the same chip. Pinned to the host
        platform there is no chip to fight over."""
        from nnstreamer_tpu.core.errors import ChipLeaseError

        spec = WorkerSpec(kind="pipeline",
                          pipeline="tensor_transform mode=typecast "
                                   "option=float32")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(ChipLeaseError, match="2 device workers"):
            WorkerPool(QueryServer.get(next(_sid)), spec, 2)
        with pytest.raises(ChipLeaseError, match="1 chip"):
            WorkerPool(QueryServer.get(next(_sid)), spec, 2, chips=[0])
        # one unleased worker owns the host; leased, one chip each
        WorkerPool(QueryServer.get(next(_sid)), spec, 1)
        WorkerPool(QueryServer.get(next(_sid)), spec, 2, chips=[0, 1])
        # echo workers never open a backend
        WorkerPool(QueryServer.get(next(_sid)), WorkerSpec(kind="echo"), 4)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        WorkerPool(QueryServer.get(next(_sid)), spec, 2)

    def test_worker_narrows_itself_to_leased_chips(self, monkeypatch):
        """What the child does first, before anything imports jax."""
        from nnstreamer_tpu.serving.worker import _narrow_to_chips

        names = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                 "TPU_PROCESS_BOUNDS", "ALLOW_MULTIPLE_LIBTPU_LOAD")
        for n in names:                  # restored by monkeypatch
            monkeypatch.delenv(n, raising=False)
        _narrow_to_chips(())
        assert not any(n in os.environ for n in names)
        _narrow_to_chips((2, 3))
        assert os.environ["TPU_VISIBLE_CHIPS"] == "2,3"
        assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
        assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
        with pytest.raises(ValueError, match="chips"):
            _narrow_to_chips((0, 1, 2))
        with pytest.raises(ValueError, match="per worker"):
            WorkerPool(QueryServer.get(next(_sid)),
                       WorkerSpec(kind="echo"), 2, chips=list(range(6)))

    def test_partition_weights_and_stats(self):
        pqs = PooledQueryServer.echo(
            sid=next(_sid), workers=2, service_ms=1.0,
            chips=list(range(8)))
        try:
            pool = pqs.pool
            assert pool.capacity_slots == 8
            assert pool.slot_weights() == {0: 4, 1: 4}
            st = pool.stats()
            owned = [tuple(w["chips"]) for w in st["workers"]]
            assert owned == [(0, 1, 2, 3), (4, 5, 6, 7)]
            assert st["chips"]["counts"] == {"free": 0, "leased": 8,
                                             "fenced": 0}
        finally:
            pqs.close()

    @pytest.mark.chaos
    def test_crashed_worker_releases_then_reowns_chips(self):
        """A dead worker's chips are fenced at reap and re-leased to
        the replacement process — 'worker wid owns chips i..j' survives
        the crash, and capacity never counts a dead chip."""
        pqs = PooledQueryServer(
            WorkerSpec(kind="echo", service_ms=1.0, crash_after_s=0.3),
            workers=2, sid=next(_sid), restart_backoff_s=0.02,
            chips=list(range(8)))
        try:
            pool = pqs.pool
            before = {w["wid"]: tuple(w["chips"])
                      for w in pool.stats()["workers"]}
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                st = pool.stats()
                if pool.chip_table.fences_total >= 4 and \
                        st["chips"]["counts"]["leased"] == 8:
                    break
                time.sleep(0.05)
            # judged on the reading that ended the wait: the workers go
            # on crashing every 0.3 s, and a later one may fall in a gap
            assert pool.chip_table.fences_total >= 4
            assert st["chips"]["counts"]["leased"] == 8
            after = {w["wid"]: tuple(w["chips"]) for w in st["workers"]}
            assert after == before           # same chips, same owners
        finally:
            pqs.close()


# -- chip-weighted scaling ----------------------------------------------------

class _WeightedStubPool:
    def __init__(self, weights):
        self._w = dict(weights)
        self._b = {i: None for i in weights}
        self.calls = []

    @property
    def size(self):
        return len(self._w)

    @property
    def capacity_slots(self):
        return sum(self._w.values())

    def slot_weights(self):
        return dict(self._w)

    def bindings(self):
        return dict(self._b)

    def rebind(self, mapping, **kw):
        self.calls.append(dict(mapping))
        self._b.update(mapping)
        return {"ok": True}


class _StubTracer:
    def __init__(self, rates):
        self.rates = rates

    def tenant_summary(self):
        return {t: {"count": 10, "rate_hz": r, "p50_ms": 1.0,
                    "p99_ms": 2.0}
                for t, r in self.rates.items()}


class TestWeightedScaler:
    def _ctrl(self, weights, rates):
        table = TenantTable.from_dict({"tenants": [
            {"name": "a", "model": "m1"},
            {"name": "b", "model": "m2"}]})
        pool = _WeightedStubPool(weights)
        return ScalingController(pool, table, _StubTracer(rates),
                                 interval_s=999.0), pool

    def test_k_chip_slot_counts_as_k_capacity(self):
        """The regression the satellite pins: a 4-chip slot is 4 units
        of allocation budget, so the hot model claims the heavy slot
        while the light model rides the 1-chip slot."""
        ctrl, pool = self._ctrl({0: 4, 1: 1}, {"a": 40.0, "b": 10.0})
        assert ctrl.tick() == {"m1": 3, "m2": 2}   # of 5 capacity units
        assert pool.bindings() == {0: "m1", 1: "m2"}

    def test_traffic_flip_moves_the_heavy_slot(self):
        ctrl, pool = self._ctrl({0: 4, 1: 1}, {"a": 40.0, "b": 10.0})
        ctrl.tick()
        assert pool.bindings()[0] == "m1"
        ctrl.tracer = _StubTracer({"a": 1.0, "b": 100.0})
        ctrl.tick()
        assert pool.bindings()[0] == "m2"

    def test_weightless_pool_budget_unchanged(self):
        # no slot_weights surface → every slot weighs 1, same plan the
        # pre-placement controller produced (regression guard)
        class _Plain(_WeightedStubPool):
            slot_weights = None
            capacity_slots = 0

        table = TenantTable.from_dict({"tenants": [
            {"name": "a", "model": "m1"},
            {"name": "b", "model": "m2"}]})
        pool = _Plain({0: 1, 1: 1, 2: 1, 3: 1})
        ctrl = ScalingController(pool, table,
                                 _StubTracer({"a": 30.0, "b": 10.0}),
                                 interval_s=999.0)
        assert ctrl.tick() == {"m1": 3, "m2": 1}


# -- metrics plane ------------------------------------------------------------

class TestReplicaMetrics:
    def test_replica_series_round_trip_and_conservation(
            self, eight_cpu_devices):
        """ISSUE 14 satellite: per-chip series survive render → parse
        with device labels intact, and Σ nns_replica_invokes_total over
        devices equals the filter's invoke count — the replica
        conservation check, as scraped."""
        bundle, dim = _bundle()
        x = np.ones((1, dim), np.float32)
        rs = _open(4, bundle)
        try:
            for _ in range(10):
                rs.invoke((x,))
            rs.fence(3, "scrape me")
            st = rs.stats()
        finally:
            rs.close()
        plan = segment_plan([("s1", 2.0), ("s2", 1.0)], 2)
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st}, segments={"p0": plan.report()})))
        inv = parsed["nns_replica_invokes_total"]
        assert inv["type"] == "counter"
        by_dev = {k: v for k, v in inv["samples"].items()}
        assert len(by_dev) == 4
        assert sum(by_dev.values()) == 10.0 \
            == sum(r["invokes"] for r in st["replicas"])
        # the fenced chip is visible as up=0 with its state label
        up = parsed["nns_replica_up"]["samples"]
        down = [k for k, v in up.items() if v == 0.0]
        assert len(down) == 1
        assert 'device="3"' in down[0] and 'state="fenced"' in down[0]
        assert parsed["nns_replica_queue_depth"]["type"] == "gauge"
        # segment plan series
        stage = parsed["nns_segment_stage_seconds"]["samples"]
        assert {('stage="0"' in k, 'stage="1"' in k)
                for k in stage} == {(True, False), (False, True)}
        bub = parsed["nns_segment_bubble_fraction"]["samples"]
        assert list(bub.values()) == [pytest.approx(0.25)]

    def test_replica_rows_in_top_view(self, eight_cpu_devices):
        bundle, dim = _bundle()
        rs = _open(2, bundle)
        try:
            rs.invoke((np.ones((1, dim), np.float32),))
            st = rs.stats()
        finally:
            rs.close()
        cur = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st})))
        lines = "\n".join(top_table({}, cur, 1.0))
        assert "nns_replica_invokes_total" in lines
        assert "nns_replica_queue_depth" in lines
