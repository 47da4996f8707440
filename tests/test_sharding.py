"""Sharded serving (serving/sharding.py): tensor/sequence parallelism
in the serving path for models bigger than one chip.

Everything runs on the 8-device emulated host mesh (markers `sharded`
+ `multichip`, fixture `eight_cpu_devices`). The acceptance checks:

- **bit-parity**: `shards=N` (N in {2, 4, 8}) is bit-identical to
  `shards=1` for the dense filter path AND paged LLM decode — the
  canonical-blocking construction makes numerics a function of the
  fixed block count, never the shard count;
- **ring prefill**: long prompts cut over to sequence-parallel ring
  attention (allclose vs blocked — a different attention order by
  design); decode from a ring-filled cache stays bit-exact;
- **group fencing**: fencing ONE member chip fences the whole shard
  group, chips land fenced in the lease ledger, and Σ group invokes ==
  frames replied holds exactly through the mid-stream fence;
- **epoch-atomic group swap**: one store update pre-warms the new
  version on EVERY shard group before anything flips — zero post-flip
  recompiles, one adopted epoch across groups;
- **typed exclusions**: chunked prefill, non-xla frameworks, explicit
  I/O overrides and W8A8 params are refused with typed errors, never
  silently served wrong;
- the `shards=` / `ring_prefill_min=` element properties, the
  TP-vs-segmentation planner (`segment_plan_tp`), and the nns_shard_*
  metric family fed from REAL ShardedReplicaSet stats.
"""

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu import PipelineRunner, TensorBuffer, parse_launch
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor
from nnstreamer_tpu.backends.xla import ModelBundle
from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.elements import AppSrc, TensorLLM, TensorSink
from nnstreamer_tpu.models.transformer import init_params
from nnstreamer_tpu.serving import compile_cache
from nnstreamer_tpu.serving.metrics import (
    metrics_snapshot, parse_prometheus, render_prometheus)
from nnstreamer_tpu.serving.placement import (
    ChipLeaseTable, apply_plan, plan_from_tracer, segment_plan_tp)
from nnstreamer_tpu.serving.sharding import (
    SUPPORTED_SHARDS, ShardedReplicaSet, validate_shards)
from nnstreamer_tpu.serving.store import get_store, reset_store
from nnstreamer_tpu.tensor.info import TensorFormat, TensorsSpec

pytestmark = [pytest.mark.sharded, pytest.mark.multichip]

#: the %8-divisible geometry the canonical blocking needs (d_model,
#: head count and vocab all split into FIXED_BLOCKS=8 blocks)
GEOM = dict(d_model=64, n_heads=8, n_layers=2, vocab=256)


@pytest.fixture(autouse=True)
def _fresh_store():
    store = reset_store()
    compile_cache.reset()
    yield store
    reset_store()
    compile_cache.reset()


@pytest.fixture(scope="module")
def llm_params():
    return init_params(**GEOM)


def _bundle(seed=3, dim=16, name="sh_mlp"):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(dim, dim)).astype(np.float32)
    return ModelBundle(fn=lambda p, x: (x @ p["w"],), params={"w": w},
                       name=name), dim


# -- dense path ---------------------------------------------------------------

class TestDenseParity:
    def test_validate_shards(self, eight_cpu_devices):
        assert SUPPORTED_SHARDS == (1, 2, 4, 8)
        for n in SUPPORTED_SHARDS:
            assert validate_shards(n) == n
        with pytest.raises(BackendError):
            validate_shards(3)
        with pytest.raises(BackendError):
            validate_shards(16)

    def test_bit_parity_across_shard_widths(self, eight_cpu_devices):
        """The dense acceptance check: one group of 1/2/4/8 chips
        produces bit-identical outputs — the shard_map body gathers
        each leaf on use and applies the UNMODIFIED model function."""
        bundle, dim = _bundle()
        x = np.linspace(-1, 1, 4 * dim,
                        dtype=np.float32).reshape(4, dim)
        ref = None
        for n in (1, 2, 4, 8):
            rs = ShardedReplicaSet.open_sharded(
                bundle, shards=n, groups=1, name=f"dp{n}")
            try:
                outs = [rs.invoke((x,)) for _ in range(3)]
            finally:
                rs.close()
            if ref is None:
                ref = np.asarray(outs[0][0])
            for o in outs:
                np.testing.assert_array_equal(np.asarray(o[0]), ref)

    def test_groups_compose_and_route(self, eight_cpu_devices):
        """2 groups x 4 chips: both groups serve, every output is
        identical, and the stats rows carry group/devices/shards."""
        bundle, dim = _bundle()
        x = np.ones((2, dim), np.float32)
        rs = ShardedReplicaSet.open_sharded(bundle, shards=4, groups=2,
                                            name="gg")
        try:
            outs = [rs.invoke((x,)) for _ in range(6)]
            st = rs.stats()
        finally:
            rs.close()
        ref = np.asarray(outs[0][0])
        for o in outs:
            np.testing.assert_array_equal(np.asarray(o[0]), ref)
        rows = st["replicas"]
        assert [r["group"] for r in rows] == [0, 1]
        assert rows[0]["devices"] == [0, 1, 2, 3]
        assert rows[1]["devices"] == [4, 5, 6, 7]
        assert st["group_size"] == 4
        assert sum(r["invokes"] for r in rows) == 6
        assert st["leases"] == {"free": 0, "leased": 8, "fenced": 0}

    def test_oversubscription_is_typed(self, eight_cpu_devices):
        bundle, _ = _bundle()
        with pytest.raises(BackendError, match="devices"):
            ShardedReplicaSet.open_sharded(bundle, shards=8, groups=2,
                                           name="over")


class TestGroupFence:
    def test_member_fence_fences_group_conserves(
            self, eight_cpu_devices):
        """Fencing ONE member chip takes the WHOLE group out: its chips
        go fenced in the lease ledger, traffic reroutes to survivors,
        and Σ group invokes == frames stays exact through the fence."""
        bundle, dim = _bundle()
        x = np.ones((2, dim), np.float32)
        rs = ShardedReplicaSet.open_sharded(bundle, shards=2, groups=4,
                                            name="fg")
        try:
            for _ in range(8):
                rs.invoke((x,))
            # chip 5 belongs to group 2 (groups lease chips in order)
            assert rs.group_of(5) == 2
            assert rs.fence_device(5, "drill")
            for _ in range(8):
                rs.invoke((x,))
            st = rs.stats()
        finally:
            rs.close()
        rows = st["replicas"]
        assert sum(r["invokes"] for r in rows) == 16
        dead = [r for r in rows if r["state"] == "fenced"]
        assert [r["group"] for r in dead] == [2]
        assert st["leases"]["fenced"] == 2      # both member chips
        assert st["fences"] == 1

    def test_fence_unknown_chip_is_noop(self, eight_cpu_devices):
        bundle, _ = _bundle()
        rs = ShardedReplicaSet.open_sharded(bundle, shards=4, groups=1,
                                            name="nf")
        try:
            assert rs.group_of(7) is None       # chips 4..7 unleased
            assert rs.fence_device(7) is False
        finally:
            rs.close()

    def test_leases_release_on_close(self, eight_cpu_devices):
        bundle, _ = _bundle()
        leases = ChipLeaseTable(range(8))
        rs = ShardedReplicaSet.open_sharded(bundle, shards=2, groups=2,
                                            leases=leases, name="rl")
        assert leases.snapshot()["counts"]["leased"] == 4
        rs.close()
        for g in range(2):
            leases.release(f"rl/g{g}")
        assert leases.snapshot()["counts"]["free"] == 8


class TestGroupSwap:
    def test_swap_is_epoch_atomic_across_groups(
            self, eight_cpu_devices):
        """One store update = the all-or-none broadcast: every shard
        group pre-warms v2 before the flip, every group adopts the same
        epoch, and post-flip traffic recompiles NOTHING."""
        store = get_store()
        store.register("shsw", lambda x: (x * 2.0,))
        store.register("shsw", lambda x: (x + 100.0,))   # v2
        x = np.linspace(-1, 1, 32, np.float32).reshape(2, 16)
        rs = ShardedReplicaSet.open_sharded("store://shsw", shards=2,
                                            groups=2, name="sw")
        try:
            for _ in range(4):
                (out,) = rs.invoke((x,))
            np.testing.assert_allclose(out, x * 2.0)  # v1 until swap
            rep = rs.swap(2)
            assert rep["handles"] == 2              # both groups warmed
            counts = rs.compile_counts()
            for _ in range(4):
                (out,) = rs.invoke((x,))
            np.testing.assert_allclose(out, x + 100.0)
            assert rs.compile_counts() == counts, "post-flip recompile"
            assert len(set(rs.adopted_epochs())) == 1
        finally:
            rs.close()

    def test_pinned_open_serves_that_version(self, eight_cpu_devices):
        store = get_store()
        store.register("shpin", lambda x: (x * 2.0,))
        store.register("shpin", lambda x: (x + 100.0,))
        x = np.ones((2, 16), np.float32)
        rs = ShardedReplicaSet.open_sharded("store://shpin@1", shards=2,
                                            groups=1, name="pin")
        try:
            (out,) = rs.invoke((x,))
            np.testing.assert_allclose(out, x * 2.0)
        finally:
            rs.close()


# -- paged LLM path -----------------------------------------------------------

def _exec(params, shards, ring_min=0, name=None):
    return PagedLLMExecutor(dict(params), n_heads=8, block_size=8,
                            num_blocks=16, max_len=64, shards=shards,
                            ring_prefill_min=ring_min,
                            name=name or f"tp{shards}")


def _serve(ex, prompt, steps=4):
    blocks = ex.cache.allocator.alloc(ex.cache.blocks_for(len(prompt)))
    lg = ex.prefill(prompt, blocks)
    outs = [np.asarray(lg)]
    tok, pos = int(np.argmax(lg)), len(prompt)
    for _ in range(steps):
        dl = ex.decode([tok], [blocks], [pos])
        outs.append(np.asarray(dl[0]))
        tok, pos = int(np.argmax(dl[0])), pos + 1
    return outs


class TestPagedLLMParity:
    def test_decode_bit_parity_across_widths(self, eight_cpu_devices,
                                             llm_params):
        """The LLM acceptance check: blocked prefill + paged decode at
        shards 2/4/8 is bit-identical to shards=1 (fixed 8-block
        combine order — numerics never see the shard count)."""
        prompt = np.random.default_rng(1).integers(
            1, 256, size=11).astype(np.int32)
        ref = None
        for n in (1, 2, 4, 8):
            ex = _exec(llm_params, n)
            try:
                outs = _serve(ex, prompt)
                st = ex.stats()
            finally:
                ex.close()
            if ref is None:
                ref = outs
                continue
            for a, b in zip(outs, ref):
                np.testing.assert_array_equal(a, b)
            assert st["shards"] == n

    def test_sharded_jit_namespace_is_tp_keyed(self, eight_cpu_devices,
                                               llm_params):
        ex = _exec(llm_params, 2)
        try:
            prompt = np.arange(1, 10, dtype=np.int32)
            _serve(ex, prompt, steps=1)
            assert ex._ns() == ("tp", 2, 0)
            kinds = {k[1] for k in ex._jits}
            assert kinds == {"prefill", "decode"}
            assert all(k[0] == ("tp", 2, 0) for k in ex._jits)
        finally:
            ex.close()

    def test_ring_prefill_cutover(self, eight_cpu_devices, llm_params):
        """Prompts >= ring_prefill_min go through the ring: allclose to
        the blocked prefill (different attention order), decode from
        the ring-filled cache bit-exact, bucket noted as llmr."""
        prompt = np.random.default_rng(7).integers(
            1, 256, size=24).astype(np.int32)
        ex_r = _exec(llm_params, 2, ring_min=16, name="ring")
        ex_b = _exec(llm_params, 2, name="ringref")
        try:
            ring = _serve(ex_r, prompt, steps=2)
            blocked = _serve(ex_b, prompt, steps=2)
            kinds = {k[1] for k in ex_r._jits}
            ref_kinds = {k[1] for k in ex_b._jits}
        finally:
            ex_r.close()
            ex_b.close()
        assert "ring" in kinds and "prefill" not in kinds
        assert ref_kinds == {"prefill", "decode"}
        np.testing.assert_allclose(ring[0], blocked[0],
                                   rtol=1e-4, atol=1e-4)
        # decode-after: same tokens either way (argmax is stable here)
        for a, b in zip(ring[1:], blocked[1:]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_short_prompt_stays_blocked(self, eight_cpu_devices,
                                        llm_params):
        ex = _exec(llm_params, 2, ring_min=16)
        try:
            _serve(ex, np.arange(1, 9, dtype=np.int32), steps=1)
            assert ex.stats()["kernel_invokes"].get("ring", 0) == 0
        finally:
            ex.close()


class TestShardedExclusions:
    def test_chunked_prefill_refused(self, eight_cpu_devices,
                                     llm_params):
        ex = _exec(llm_params, 2)
        try:
            with pytest.raises(BackendError, match="ring"):
                ex.prefill_chunk(np.arange(1, 9, dtype=np.int32),
                                 0, [1])
        finally:
            ex.close()

    def test_engine_refuses_chunk_plus_shards(self, eight_cpu_devices,
                                              llm_params):
        from nnstreamer_tpu.llm import LLMEngine

        with pytest.raises(BackendError, match="exclusive"):
            LLMEngine(llm_params, n_heads=8, block_size=8,
                      num_blocks=16, max_len=64, shards=2,
                      prefill_chunk=8)

    def test_pallas_with_shards_refused(self, eight_cpu_devices,
                                        llm_params):
        with pytest.raises(BackendError, match="single-chip"):
            PagedLLMExecutor(dict(llm_params), n_heads=8, block_size=8,
                             num_blocks=16, max_len=64, shards=2,
                             paged_kernel="pallas", name="pk")

    def test_quantized_params_refused_float_only(
            self, eight_cpu_devices, llm_params):
        from nnstreamer_tpu.models.quant import quantize_transformer

        qp = quantize_transformer(llm_params)
        with pytest.raises(BackendError, match="float-only"):
            _exec(qp, 2)


# -- elements -----------------------------------------------------------------

def _run_filter(extra, frames=6, dim=16):
    pipe = parse_launch(
        f"appsrc name=src dims={dim} types=float32 ! "
        f"tensor_filter name=f model=store://shf {extra} ! "
        f"tensor_sink name=out")
    runner = PipelineRunner(pipe)
    runner.start()
    src, sink = pipe.get("src"), pipe.get("out")
    try:
        for i in range(frames):
            src.push(TensorBuffer.of(
                np.full((dim,), float(i), np.float32), pts=i))
        src.end()
        runner.wait(60)
    finally:
        runner.stop()
    return ({int(b.pts): np.asarray(b.tensors[0]) for b in sink.results},
            pipe.get("f"))


class TestFilterElement:
    def test_shards_prop_bit_parity_and_stats(self, eight_cpu_devices):
        get_store().register("shf", lambda x: (x * 2.0 + 1.0,))
        base, _ = _run_filter("")
        got, f = _run_filter("shards=2 devices=4")
        assert got.keys() == base.keys()
        for pts, ref in base.items():
            np.testing.assert_array_equal(got[pts], ref)
        st = f.extra_stats()
        assert st["shards"] == 2
        assert st["shard_groups"] == 2
        assert st["replica_invokes"] == len(base)
        assert st["leases"]["leased"] == 4

    def test_unsupported_width_fails_negotiation(self,
                                                 eight_cpu_devices):
        from nnstreamer_tpu.core.errors import NegotiationError

        get_store().register("shf", lambda x: (x * 2.0,))
        with pytest.raises(NegotiationError):
            _run_filter("shards=3")

    def test_explicit_io_overrides_decline_sharding(
            self, eight_cpu_devices):
        """Explicit I/O override props are single-backend concerns: the
        filter declines sharding and serves single-chip (soft decline,
        not failure) — outputs stay correct."""
        get_store().register("shf", lambda x: (x * 2.0,))
        base, _ = _run_filter("")
        got, f = _run_filter("shards=2 output=16 outputtype=float32")
        for pts, ref in base.items():
            np.testing.assert_array_equal(got[pts], ref)
        assert "shards" not in f.extra_stats()


def _run_llm(prompt, **llm_props):
    src = AppSrc(name="src", spec=TensorsSpec(
        tensors=(), format=TensorFormat.FLEXIBLE))
    llm = TensorLLM(name="g", model="store://shllm", n_heads=8,
                    block_size=8, num_blocks=16, max_len=64,
                    **llm_props)
    sink = TensorSink(name="out")
    pipe = nns.Pipeline()
    for e in (src, llm, sink):
        pipe.add(e)
    pipe.link(src, llm)
    pipe.link(llm, sink)
    runner = PipelineRunner(pipe)
    runner.start()
    try:
        src.push(TensorBuffer(tensors=(prompt,), pts=0,
                              meta={"llm": {"request_id": "r0",
                                            "max_new_tokens": 6}}))
        src.end()
        runner.wait(120)
    finally:
        runner.stop()
    toks = [int(t) for b in sink.results
            for t in np.asarray(b.tensors[0]).reshape(-1)]
    return toks, llm


class TestLLMElement:
    def test_shards_prop_token_parity(self, eight_cpu_devices,
                                      llm_params):
        """tensor_llm shards=N serves the IDENTICAL token stream as the
        single-chip element, and leases its chips as one group."""
        get_store().register(
            "shllm", ModelBundle(fn=None, params=llm_params))
        prompt = np.random.default_rng(3).integers(
            1, 256, 12).astype(np.int32)
        t0, _ = _run_llm(prompt)
        t2, g2 = _run_llm(prompt, shards=2, ring_prefill_min=32)
        t4, _ = _run_llm(prompt, shards=4)
        assert len(t0) == 6
        assert t0 == t2 == t4
        st = g2.extra_stats()
        assert st["executor"]["shards"] == 2
        assert st["leases"] == {"free": 8, "leased": 0, "fenced": 0}

    def test_chunk_plus_shards_fails_negotiation(self,
                                                 eight_cpu_devices,
                                                 llm_params):
        from nnstreamer_tpu.core.errors import NegotiationError

        get_store().register(
            "shllm", ModelBundle(fn=None, params=llm_params))
        prompt = np.arange(1, 9, dtype=np.int32)
        with pytest.raises(NegotiationError):
            _run_llm(prompt, shards=2, prefill_chunk=8)
        with pytest.raises(NegotiationError):
            _run_llm(prompt, ring_prefill_min=16)   # ring without shards


# -- TP-vs-segmentation planner -----------------------------------------------

class TestPlanTP:
    def test_dominant_stage_gets_tp_not_cuts(self):
        plan = segment_plan_tp(
            [("pre", 0.1), ("big", 8.0), ("post", 0.1)], 8)
        assert plan.tp == [8]
        assert len(plan.stages) == 1
        assert plan.report()["chips_total"] == 8

    def test_balanced_chain_gets_cuts_not_tp(self):
        plan = segment_plan_tp([(f"e{i}", 1.0) for i in range(4)], 4)
        assert plan.tp == [1, 1, 1, 1]
        assert len(plan.stages) == 4
        assert plan.bubble_fraction == 0.0

    def test_low_efficiency_never_shards(self):
        # at eff <= 0.5 a doubling buys nothing: 2 * 0.5 = 1x
        plan = segment_plan_tp([("big", 8.0), ("small", 0.1)], 8,
                               tp_efficiency=0.5)
        assert all(t == 1 for t in plan.tp)

    def test_mixed_profile_mixes(self):
        plan = segment_plan_tp(
            [("pre", 0.2), ("h1", 4.0), ("h2", 4.0)], 8)
        assert sum(plan.tp) <= 8
        assert max(plan.tp) >= 2          # somebody got shards
        assert len(plan.stages) >= 2      # and the chain still cut
        # devices are contiguous group starts
        assert plan.devices == [0, plan.tp[0]][:len(plan.stages)]

    def test_plan_from_tracer_tp_kwarg(self, eight_cpu_devices):
        class _T:
            active = True

            def hists(self):
                return {"a": {"sum": 8.0, "count": 1},
                        "b": {"sum": 0.1, "count": 1}}

        plan = plan_from_tracer(_T(), ["a", "b"], 8, tp_efficiency=0.7)
        assert plan.source == "tracer"
        assert max(plan.tp) > 1
        # default stays the pure-segmentation DP (no tp field set)
        plain = plan_from_tracer(_T(), ["a", "b"], 8)
        assert plain.tp == []

    def test_apply_plan_sets_shards_prop(self, eight_cpu_devices):
        get_store().register("shf", lambda x: (x * 2.0,))
        pipe = parse_launch(
            "appsrc name=src dims=16 types=float32 ! "
            "tensor_filter name=f model=store://shf ! "
            "tensor_sink name=out")
        plan = segment_plan_tp([("f", 8.0)], 8)
        assert plan.tp == [8]
        pinned = apply_plan(pipe, plan)
        assert pinned == 1
        assert pipe.get("f").props["shards"] == 8


# -- metrics from real stats --------------------------------------------------

class TestShardMetrics:
    def test_real_stats_round_trip_conservation(self,
                                                eight_cpu_devices):
        """The nns_shard_* family fed from a LIVE ShardedReplicaSet:
        Σ shard group invokes == the filter's invoke count, from one
        render → parse cycle."""
        bundle, dim = _bundle()
        x = np.ones((2, dim), np.float32)
        rs = ShardedReplicaSet.open_sharded(bundle, shards=2, groups=2,
                                            name="ms")
        try:
            for _ in range(10):
                rs.invoke((x,))
            st = rs.stats()
        finally:
            rs.close()
        parsed = parse_prometheus(render_prometheus(metrics_snapshot(
            replicas={"f": st})))
        fam = parsed["nns_shard_group_invokes_total"]["samples"]
        assert sum(fam.values()) == 10.0
        assert parsed["nns_shard_group_size"]["samples"][
            'nns_shard_group_size{filter="f"}'] == 2.0
        leases = parsed["nns_shard_leased_chips"]["samples"]
        assert leases['nns_shard_leased_chips{filter="f",'
                      'state="leased"}'] == 4.0
        ups = parsed["nns_shard_group_up"]["samples"]
        assert all(v == 1.0 for v in ups.values())
