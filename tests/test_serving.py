"""Model store & zero-downtime hot-swap serving (docs/serving.md):
versioned registry + ``store://`` refs, epoch-based swap with pre-warmed
buckets (recompile-free hot path), canary routing, per-version stats,
and the persistent compile cache manifest.

Models are tiny jax callables so every version is distinguishable by
output value alone: v1 = x*2, v2 = x*3 + 10."""

import json
import os
import time

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu import PipelineRunner, TensorBuffer, parse_launch
from nnstreamer_tpu.backends.xla import XLABackend
from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.serving import compile_cache
from nnstreamer_tpu.serving.store import (
    get_store,
    parse_store_ref,
    reset_store,
)


def _v1(x):
    return (x * 2.0,)


def _v2(x):
    return (x * 3.0 + 10.0,)


V1 = 2.0    # value of v1 on an all-ones frame
V2 = 13.0   # value of v2 on an all-ones frame


@pytest.fixture(autouse=True)
def _fresh_store():
    store = reset_store()
    compile_cache.reset()
    yield store
    reset_store()
    compile_cache.reset()


def _open_backend(ref, **props):
    b = XLABackend()
    b.open({"model": ref, "accelerator": "", "canary_seed": 0, **props})
    return b


def _push_ones(src, n, shape=(4,)):
    for _ in range(n):
        src.push(TensorBuffer.of(np.ones(shape, np.float32)))


def _out_vals(sink):
    return [float(np.asarray(b.tensors[0]).ravel()[0]) for b in sink.results]


def _wait_for(cond, timeout=10.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timed out waiting: {what}"
        time.sleep(0.01)


# -- store:// reference grammar ----------------------------------------------

class TestParseStoreRef:
    def test_track_current(self):
        r = parse_store_ref("store://det")
        assert (r.name, r.version, r.canary_version) == ("det", None, None)

    def test_latest_is_track(self):
        assert parse_store_ref("store://det@latest").version is None

    def test_pinned_int(self):
        assert parse_store_ref("store://det@3").version == 3

    def test_pinned_alias(self):
        assert parse_store_ref("store://det@prod").version == "prod"

    def test_canary(self):
        r = parse_store_ref("store://det@2:0.05")
        assert (r.canary_version, r.canary_ratio) == (2, 0.05)
        assert r.version is None          # the 95% side tracks current

    @pytest.mark.parametrize("bad,msg", [
        ("zoo://det", "not a store reference"),
        ("store://", "no model name"),
        ("store://det@2:zzz", "bad canary ratio"),
        ("store://det@2:1.5", "out of range"),
        ("store://det@2:0", "out of range"),
        ("store://det@latest:0.2", "needs an explicit version"),
    ])
    def test_errors(self, bad, msg):
        with pytest.raises(BackendError, match=msg):
            parse_store_ref(bad)


# -- registry ----------------------------------------------------------------

class TestRegistry:
    def test_register_auto_versions_first_is_current(self, _fresh_store):
        store = _fresh_store
        assert store.register("det", _v1) == 1
        assert store.register("det", _v2) == 2
        # zero-downtime contract: registration never changes what serves
        assert store.entry("det").current == 1

    def test_update_default_latest(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        rep = store.update("det")
        assert (rep["from_version"], rep["to_version"]) == (1, 2)
        assert store.entry("det").current == 2
        assert store.entry("det").epoch == 1

    def test_duplicate_version_raises_naming_collision(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1, version=3)
        with pytest.raises(BackendError, match=r"'det'@3.*immutable"):
            store.register("det", _v2, version=3)

    def test_alias_pins(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        store.alias("det", "prod", 1)
        assert store.entry("det").resolve_version("prod") == 1
        with pytest.raises(BackendError, match="no version alias"):
            store.entry("det").resolve_version("staging")

    def test_unknown_name_lists_registered(self, _fresh_store):
        _fresh_store.register("det", _v1)
        with pytest.raises(BackendError, match="no model named 'nope'"):
            _fresh_store.entry("nope")

    def test_describe(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        store.update("det")
        d = store.describe("det")
        assert d["current"] == 2 and d["epoch"] == 1
        assert sorted(d["versions"]) == [1, 2]
        assert len(d["swaps"]) == 1

    def test_zoo_builtin_seeds_as_version_zero(self, _fresh_store):
        e = _fresh_store.entry("mobilenet_v2")
        assert 0 in e.versions
        assert e.versions[0].source == "zoo://mobilenet_v2"
        # lazy: describing must not build the actual model
        assert _fresh_store.describe("mobilenet_v2")["versions"][0][
            "built"] is False

    def test_zoo_duplicate_name_raises(self):
        from nnstreamer_tpu.models.zoo import register_model

        with pytest.raises(BackendError, match="already registered"):
            register_model("mobilenet_v2")(lambda **kw: None)

    def test_store_ref_cannot_nest_as_version_source(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("indirect", "store://det")
        with pytest.raises(BackendError, match="cannot nest"):
            store.update("indirect")


# -- hot swap mid-stream -----------------------------------------------------

class TestSwapMidStream:
    def test_no_torn_version_and_report(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        pipe = parse_launch(
            "appsrc name=src dims=4 types=float32 ! "
            "tensor_filter name=f model=store://det ! tensor_sink name=out")
        runner = PipelineRunner(pipe, trace=True)
        runner.start()
        src, sink, f = pipe.get("src"), pipe.get("out"), pipe.get("f")
        try:
            _push_ones(src, 10)
            _wait_for(lambda: len(sink.results) >= 10, what="v1 frames")
            rep = store.update("det", wait_s=None)
            _push_ones(src, 10)
            src.end()
            runner.wait(30)
        finally:
            runner.stop()
        assert rep["prewarmed_buckets"] >= 1
        vals = _out_vals(sink)
        assert len(vals) == 20
        # every output is exactly one version's math — never a blend —
        # and the flip is monotone (old then new, adoption is ordered)
        assert set(vals) == {V1, V2}
        flip = vals.index(V2)
        assert all(v == V1 for v in vals[:flip])
        assert all(v == V2 for v in vals[flip:])
        # observability: swap rendered in the report + per-version rows
        report = runner.report()
        assert "model swaps" in report
        assert "v1 → v2" in report
        st = runner.stats()["f"]
        assert st["backend_v1_invokes"] == 10
        assert st["backend_v2_invokes"] == 10
        assert st["backend_swaps"] == 1

    def test_swap_through_dyn_batch_path(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        pipe = parse_launch(
            "appsrc name=src dims=4 types=float32 ! "
            "tensor_batch max-batch=4 max-latency-ms=20 ! "
            "tensor_filter model=store://det ! tensor_unbatch ! "
            "tensor_sink name=out")
        runner = PipelineRunner(pipe)
        runner.start()
        src, sink = pipe.get("src"), pipe.get("out")
        try:
            _push_ones(src, 12)
            _wait_for(lambda: len(sink.results) >= 12, what="v1 frames")
            store.update("det")
            _push_ones(src, 12)
            src.end()
            runner.wait(30)
        finally:
            runner.stop()
        vals = _out_vals(sink)
        assert len(vals) == 24
        assert set(vals) == {V1, V2}
        flip = vals.index(V2)
        assert all(v == V1 for v in vals[:flip])
        assert all(v == V2 for v in vals[flip:])

    def test_pinned_ref_is_immune_to_swap(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        b = _open_backend("store://det@1")
        try:
            assert b.tracks_store_epoch is False
            store.update("det")
            out = b.invoke((np.ones(4, np.float32),))
            assert float(np.asarray(out[0])[0]) == V1
            assert b.swap_count == 0
        finally:
            b.close()

    def test_swap_barrier_completes_under_traffic(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        pipe = parse_launch(
            "videotestsrc width=2 height=2 num-buffers=400 ! "
            "tensor_converter ! "
            "tensor_filter name=f model=store://det ! tensor_sink name=out")
        runner = PipelineRunner(pipe)
        runner.start()
        sink = pipe.get("out")
        try:
            _wait_for(lambda: len(sink.results) >= 5, what="traffic")
            rep = store.update("det", wait_s=10.0)
        finally:
            runner.wait(30)
            runner.stop()
        assert rep["barrier_ok"] is True
        assert pipe.get("f").backend.adopted_epoch == rep["epoch"]


# -- chaos: swap with fault injection, conservation across the flip ----------

class TestChaosSwap:
    def test_conservation_across_flip(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        pipe = parse_launch(
            "appsrc name=src dims=4 types=float32 ! "
            "tensor_fault name=flt mode=raise probability=0.08 seed=7 "
            "error-policy=skip ! "
            "tensor_filter model=store://det ! tensor_sink name=out")
        runner = PipelineRunner(pipe)
        runner.start()
        src, sink = pipe.get("src"), pipe.get("out")
        try:
            _push_ones(src, 40)
            _wait_for(lambda: len(sink.results) >= 20, what="pre-swap flow")
            store.update("det")
            _push_ones(src, 40)
            src.end()
            runner.wait(30)
        finally:
            runner.stop()
        st = runner.stats()["flt"]
        assert sink.eos.is_set()
        # PR-3 conservation invariant holds across the epoch flip:
        # emitted + skipped + dropped == generated
        assert len(sink.results) + st["skipped"] + st["dropped"] == 80
        assert st["errors"] > 0 and st["skipped"] == st["errors"]
        # surviving frames still carry exactly one version's math
        vals = _out_vals(sink)
        assert set(vals) <= {V1, V2} and V2 in vals


# -- pre-warmed swap: recompile-free hot path --------------------------------

class TestPrewarm:
    def _serve_buckets(self, b):
        """Serve two dyn_batch buckets + one fixed bucket; return the
        math value observed (all-ones input)."""
        vals = set()
        for n in (3, 6):
            out = b.invoke_batched((np.ones((n, 4), np.float32),), n,
                                   keepdims=(False,))
            vals.add(float(np.asarray(out[0])[0, 0]))
        out = b.invoke((np.ones(4, np.float32),))
        vals.add(float(np.asarray(out[0])[0]))
        assert len(vals) == 1
        return vals.pop()

    def test_prewarmed_swap_hits_cache_only(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        b = _open_backend("store://det")
        try:
            assert self._serve_buckets(b) == V1
            store.register("det", _v2)
            rep = store.update("det")
            # all three served buckets compiled before the flip
            assert rep["prewarmed_buckets"] == 3
            cc0, ch0 = b.compile_count, b.cache_hits
            assert self._serve_buckets(b) == V2
            # the acceptance gate: same bucket set, post-flip, is pure
            # cache hits — zero recompiles on the hot path
            assert b.compile_count == cc0
            assert b.cache_hits == ch0 + 3
            assert b.swap_count == 1
        finally:
            b.close()

    def test_unwarmed_swap_recompiles(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        b = _open_backend("store://det")
        try:
            self._serve_buckets(b)
            store.register("det", _v2)
            rep = store.update("det", prewarm=False)
            assert rep["prewarmed_buckets"] == 0
            cc0 = b.compile_count
            assert self._serve_buckets(b) == V2
            assert b.compile_count == cc0 + 3   # the spike prewarm avoids
        finally:
            b.close()

    def test_incompatible_version_aborts_before_flip(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        b = _open_backend("store://det")
        try:
            self._serve_buckets(b)

            def bad(x):
                return (x @ np.ones((5, 5), np.float32),)   # wrong shape

            store.register("det", bad)
            with pytest.raises(BackendError, match="swap aborted"):
                store.update("det")
            # nothing flipped: still serving v1
            assert store.entry("det").current == 1
            assert self._serve_buckets(b) == V1
            assert b.swap_count == 0
        finally:
            b.close()


# -- canary routing ----------------------------------------------------------

class TestCanary:
    def _routed_vals(self, seed, n=300):
        b = _open_backend("store://det@2:0.25", canary_seed=seed)
        try:
            vals = []
            for _ in range(n):
                out = b.invoke((np.ones(4, np.float32),))
                vals.append(float(np.asarray(out[0])[0]))
            return vals
        finally:
            b.close()

    def test_ratio_within_tolerance_and_deterministic(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        vals = self._routed_vals(seed=7)
        share = vals.count(V2) / len(vals)
        assert 0.15 < share < 0.35      # 0.25 target, seeded sample
        # determinism: same seed → the exact same routing sequence
        assert self._routed_vals(seed=7) == vals
        assert self._routed_vals(seed=8) != vals

    def test_per_version_stats_split(self, _fresh_store):
        store = _fresh_store
        store.register("det", _v1)
        store.register("det", _v2)
        b = _open_backend("store://det@2:0.25", canary_seed=3)
        try:
            for _ in range(100):
                b.invoke((np.ones(4, np.float32),))
            vs = b.version_stats()
            assert vs[1]["invokes"] + vs[2]["invokes"] == 100
            assert vs[2]["invokes"] > 0
            assert vs[1]["errors"] == vs[2]["errors"] == 0
            assert vs[1]["p95_us"] > 0
        finally:
            b.close()

    def test_canary_version_must_differ_from_base(self, _fresh_store):
        _fresh_store.register("det", _v1)
        with pytest.raises(BackendError, match="canary"):
            _open_backend("store://det@1:0.25")


# -- persistent compile cache + bucket manifest ------------------------------

class TestCompileCache:
    def test_manifest_roundtrip_and_warm_start(self, _fresh_store,
                                               tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set the directory is jax's to
        read: enabling the cache leaves jax.config untouched and the
        manifest lands in that same directory."""
        monkeypatch.setenv("NNSTREAMER_TPU_SERVING_COMPILE_CACHE", "1")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        compile_cache.reset()
        import jax
        before = jax.config.jax_compilation_cache_dir
        try:
            store = _fresh_store
            store.register("det", _v1)
            b = _open_backend("store://det")
            assert compile_cache.cache_dir() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
            b.invoke_batched((np.ones((3, 4), np.float32),), 3,
                             keepdims=(False,))
            b.invoke((np.ones(4, np.float32),))
            b.close()
            with open(tmp_path / "manifest.json") as f:
                man = json.load(f)
            kinds = sorted(r["kind"] for r in man["det@1"])
            assert kinds == ["dynb", "fix"]

            # "next process": fresh store + backend replay the manifest
            store = reset_store()
            store.register("det", _v1)
            b2 = _open_backend("store://det")
            assert b2.warm_start() == 2
            cc0 = b2.compile_count
            out = b2.invoke_batched((np.ones((3, 4), np.float32),), 3,
                                    keepdims=(False,))
            assert float(np.asarray(out[0])[0, 0]) == V1
            b2.invoke((np.ones(4, np.float32),))
            assert b2.compile_count == cc0    # warm start covered both
            b2.close()
        finally:
            compile_cache.reset()

    def test_unset_resolves_to_checkout(self, tmp_path, monkeypatch):
        """Without the variable the cache is <checkout>/.jax_cache,
        whatever HOME and the working directory are."""
        import nnstreamer_tpu

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        monkeypatch.chdir(tmp_path)
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(nnstreamer_tpu.__file__)))
        assert compile_cache.resolve_dir() == (
            os.path.join(checkout, ".jax_cache"), False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.resolve_dir() == ("/some/dir", True)

    def test_disabled_by_default(self, _fresh_store):
        assert compile_cache.maybe_enable_compile_cache() is False
        assert compile_cache.cache_dir() is None
        store = _fresh_store
        store.register("det", _v1)
        b = _open_backend("store://det")
        try:
            assert b.warm_start() == 0     # nothing recorded, no replay
        finally:
            b.close()


# -- guard rails -------------------------------------------------------------

class TestGuards:
    def test_reload_on_store_filter_points_to_update(self, _fresh_store):
        _fresh_store.register("det", _v1)
        b = _open_backend("store://det")
        try:
            with pytest.raises(BackendError, match="ModelStore.update"):
                b.reload(_v2)
        finally:
            b.close()

    def test_shared_key_rejected(self, _fresh_store):
        _fresh_store.register("det", _v1)
        b = XLABackend()
        with pytest.raises(BackendError, match="shared-tensor-filter-key"):
            b.open({"model": "store://det", "accelerator": "",
                    "canary_seed": 0, "shared_tensor_filter_key": "k"})

    def test_cli_models_list_and_describe(self, _fresh_store, capsys):
        from nnstreamer_tpu.__main__ import main

        _fresh_store.register("det", _v1)
        assert main(["models", "list"]) == 0
        assert "store://det" in capsys.readouterr().out
        assert main(["models", "describe", "det"]) == 0
        assert '"current": 1' in capsys.readouterr().out
        assert main(["models", "swap", "det", "1"]) == 0
        assert '"to_version": 1' in capsys.readouterr().out
