"""Continuous-batching LLM serving (nnstreamer_tpu/llm, tensor_llm).

The gate that matters: paged decode must equal `transformer.generate`
token-for-token at temperature 0 — the paged formulation (gathered KV,
per-row positions, scratch-block padding) is only a serving layout
change, never a numerics change. Around it: block-allocator
invariants, admission under a full pool (queue, never crash), EOS /
max-token retirement returning blocks, the manifest round-trip for LLM
buckets, and the tier-1 smoke pushing concurrent requests through the
tensor_llm element.
"""

import time

import numpy as np
import pytest

import nnstreamer_tpu as nns
from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.elements import AppSrc, TensorLLM, TensorSink
from nnstreamer_tpu.llm import BlockAllocator, LLMEngine
from nnstreamer_tpu.models.transformer import generate, init_params
from nnstreamer_tpu.serving.store import get_store, reset_store
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.tensor.info import TensorFormat, TensorsSpec


@pytest.fixture(scope="module")
def params():
    return init_params(vocab=61, d_model=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, seed=0)


@pytest.fixture(scope="module")
def engine(params):
    """Shared continuous engine (module scope amortizes jit compiles)."""
    return LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                     max_batch=4, max_len=64)


def _ref(params, prompt, n):
    return np.asarray(
        generate(params, np.asarray(prompt)[None, :], n,
                 n_heads=4, max_len=64))[0, len(prompt):]


# -- block allocator ---------------------------------------------------------

def test_allocator_alloc_free_invariants():
    a = BlockAllocator(8)            # 1 scratch + 7 usable
    assert a.total == 7 and a.free == 7 and a.used == 0
    got = a.alloc(3, owner="r1")
    assert len(got) == 3 and 0 not in got        # scratch never granted
    assert a.used == 3 and a.high_water == 3
    # all-or-nothing: 5 > 4 free -> None, nothing consumed
    assert a.alloc(5) is None
    assert a.free == 4 and a.failed_allocs == 1
    a.free_blocks(got)
    assert a.free == 7 and a.used == 0
    assert a.high_water == 3                     # high-water sticks
    # freed blocks are reusable
    again = a.alloc(7)
    assert sorted(set(again)) == sorted(again) and len(again) == 7


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    got = a.alloc(2)
    a.free_blocks(got)
    with pytest.raises(ValueError):
        a.free_blocks(got)
    with pytest.raises(ValueError):
        a.free_blocks([0])           # scratch was never granted


def test_allocator_rejects_degenerate_pool():
    with pytest.raises(ValueError):
        BlockAllocator(1)            # scratch only: nothing allocatable


def test_allocator_stats_utilization():
    a = BlockAllocator(11)
    a.alloc(5)
    s = a.stats()
    assert s["blocks_total"] == 10 and s["blocks_used"] == 5
    assert s["utilization"] == 0.5


# -- manifest round-trip -----------------------------------------------------

def test_llm_bucket_manifest_roundtrip():
    from nnstreamer_tpu.serving.compile_cache import (
        _bucket_from_json, _bucket_to_json)

    for bk in (("llmp", 16), ("llmd", 4), ("llmp_chunk", 32)):
        jb = _bucket_to_json(bk)
        assert jb is not None
        assert _bucket_from_json(jb) == bk
    # the existing kinds still round-trip (no regression)
    fix = ("fix", ((1, 3), "float32"))
    assert _bucket_from_json(_bucket_to_json(fix)) == fix


# -- decode parity vs transformer.generate -----------------------------------

@pytest.fixture(params=["ahead", "resolved"])
def order(request, monkeypatch):
    """The two orders of a step. `ahead`: launch, then read the launch
    before (what greedy rows on one chip get). `resolved`: read, sample
    on the host, then launch (what a step with a sampled row gets),
    here by steering the engine's own choice. The served tokens are the
    same in both, and generate()'s."""
    if request.param == "resolved":
        monkeypatch.setattr(LLMEngine, "_runs_ahead",
                            lambda self, pending: False)
    return request.param


def _ahead_since(eng, before, order):
    """Launches made ahead of a read since `before`: all but the first
    of a drained burst under `ahead`, none under `resolved`."""
    made = eng.lookahead_steps - before
    return made > 0 if order == "ahead" else made == 0


def test_paged_parity_single_request(engine, params, order):
    prompt = np.array([5, 17, 3], np.int32)
    before = engine.lookahead_steps
    req = engine.submit(prompt, max_new_tokens=8)
    engine.drain()
    assert req.finish_reason == "length"
    assert np.array_equal(np.array(req.tokens), _ref(params, prompt, 8))
    assert _ahead_since(engine, before, order)
    assert engine.lookahead_discarded == 0


def test_paged_parity_interleaved_lengths(engine, params, order):
    """Concurrent requests with different prompt lengths interleave in
    one continuous batch; each stream must still match its own
    single-sequence generate() bit-for-bit."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (1, 4, 7, 11)]
    before = engine.lookahead_steps
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.drain()
    for p, r in zip(prompts, reqs):
        assert np.array_equal(np.array(r.tokens), _ref(params, p, 6)), \
            f"plen={len(p)}"
    # every retirement returned its blocks
    assert engine.cache.allocator.used == 0
    assert _ahead_since(engine, before, order)


def test_paged_parity_staggered_admission(engine, params, order):
    """A request admitted mid-flight (merged into a running decode
    batch) produces the same tokens as one served alone."""
    before = engine.lookahead_steps
    a = engine.submit(np.array([9, 2, 40, 11], np.int32),
                      max_new_tokens=10)
    engine.step()                    # a is prefilled + decoding
    b = engine.submit(np.array([33, 1], np.int32), max_new_tokens=5)
    engine.drain()
    assert np.array_equal(np.array(a.tokens),
                          _ref(params, a.prompt, 10))
    assert np.array_equal(np.array(b.tokens),
                          _ref(params, b.prompt, 5))
    assert _ahead_since(engine, before, order)


# -- admission / retirement --------------------------------------------------

def test_admission_queues_when_pool_full(params, order):
    """More requests than the pool can hold: latecomers queue (never
    crash) and complete as retirements free blocks."""
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=8,
                    max_batch=8, max_len=16)
    # each request needs ceil((2+6)/4)=2 blocks; pool has 7 usable ->
    # at most 3 resident; 6 requests => queueing is guaranteed
    reqs = [eng.submit(np.array([i + 1, i + 2], np.int32),
                       max_new_tokens=6) for i in range(6)]
    eng.drain()
    assert all(r.finish_reason == "length" for r in reqs)
    assert all(len(r.tokens) == 6 for r in reqs)
    assert eng.admission_blocked > 0
    assert eng.cache.allocator.failed_allocs > 0
    assert eng.cache.allocator.used == 0
    for r in reqs:                   # queueing must not corrupt streams
        assert np.array_equal(
            np.array(r.tokens),
            np.asarray(generate(params, r.prompt[None, :], 6,
                                n_heads=4, max_len=16))[0, 2:])


def test_submit_rejects_unservable_request(params):
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=8,
                    max_batch=2, max_len=16)
    with pytest.raises(BackendError):
        eng.submit(np.arange(10, dtype=np.int32), max_new_tokens=20)
    with pytest.raises(BackendError):
        eng.submit(np.array([], np.int32))
    with pytest.raises(BackendError):
        eng.submit(np.array([1], np.int32), max_new_tokens=0)


def test_eos_retires_and_frees_blocks(engine, params):
    """Run once to learn a token the model actually emits, then rerun
    with that token as eos_id: the request must stop AT the eos token
    and return its blocks."""
    prompt = np.array([12, 30], np.int32)
    probe = engine.submit(prompt, max_new_tokens=8)
    engine.drain()
    eos = probe.tokens[3]            # a token known to appear mid-stream
    req = engine.submit(prompt, max_new_tokens=8, eos_id=eos)
    engine.drain()
    assert req.finish_reason == "eos"
    assert req.tokens[-1] == eos
    assert len(req.tokens) == probe.tokens.index(eos) + 1
    assert engine.cache.allocator.used == 0


def test_eos_with_the_next_step_in_flight_discards_one_token(params):
    """A row stops on its eos_id when the launch after is already made:
    that launch's token for it is discarded and counted, its blocks go
    back once, and the request that takes them is served right."""
    # 6 usable blocks of 4 slots; a request of 2 + 8 comes to hold 3 if
    # it runs its budget out, which is all admission knows: two fit
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=7,
                    max_batch=4, max_len=16)
    gave = []
    release = eng.cache.release
    eng.cache.release = lambda blocks, slot: (
        gave.append(list(blocks)), release(blocks, slot))
    prompt = np.array([12, 30], np.int32)
    probe = _ref(params, prompt, 8)
    eos = int(probe[3])
    stop = [int(t) for t in probe].index(eos)
    a = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    b = eng.submit(np.array([7, 19], np.int32), max_new_tokens=8)
    c = eng.submit(np.array([41, 5], np.int32), max_new_tokens=8)
    eng.step()
    assert len(a.block_table) == 1 and c.state == "queued"
    while a.state != "done":
        eng.step()
    a_blocks, = gave                 # back once, grown to what it wrote
    assert len(a_blocks) == -(-(2 + stop + 1) // 4)
    # a's stop was read after the next launch was made, with a in it
    assert a.finish_reason == "eos" and len(a.tokens) == stop + 1
    assert a.ahead == 1 and eng.lookahead_discarded == 0
    eng.step()                       # reads that launch; admits c
    assert a.ahead == 0 and eng.lookahead_discarded == 1
    assert set(c.block_table) <= set(a_blocks)
    eng.drain()
    assert [int(t) for t in a.tokens] == [int(t) for t in probe[:stop + 1]]
    for r in (b, c):
        assert np.array_equal(
            np.array(r.tokens),
            np.asarray(generate(params, r.prompt[None, :], 8,
                                n_heads=4, max_len=16))[0, 2:])
    st = eng.stats()
    assert st["lookahead_discarded"] == 1
    # a discarded row is a launch's row, not a token
    assert st["tokens_out"] == sum(len(r.tokens) for r in (a, b, c))
    assert st["cache"]["blocks_used"] == 0 and eng.admission_blocked > 0


def test_a_sampled_row_puts_its_steps_in_the_resolved_order(params):
    """While a row with temperature > 0 is live every step resolves
    before it launches, and the row's tokens are those of the same seed
    served alone (every step resolved); greedy rows beside it, before it
    and after it are generate()'s."""
    kw = dict(max_new_tokens=5, temperature=0.8, top_k=5, seed=11)
    prompt = np.array([3, 8, 21], np.int32)
    alone = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                      max_batch=4, max_len=64)
    want = alone.submit(prompt, **kw)
    alone.drain()
    assert alone.lookahead_steps == 0 and len(want.tokens) == 5

    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=4, max_len=64)
    g1 = eng.submit(np.array([9, 2, 40, 11], np.int32), max_new_tokens=14)
    eng.step()
    eng.step()
    assert eng._ahead is not None and eng.lookahead_steps == 1
    s = eng.submit(prompt, **kw)
    eng.step()                       # reads g1's launch, then resolves
    assert eng._ahead is None and len(s.tokens) == 2
    g2 = eng.submit(np.array([33, 1], np.int32), max_new_tokens=6)
    while s.state != "done":
        eng.step()
        assert eng._ahead is None and g1.ahead == 0
    assert eng.lookahead_steps == 1
    eng.drain()                      # greedy rows only: ahead again
    assert eng.lookahead_steps > 1
    assert s.tokens == want.tokens
    assert np.array_equal(np.array(g1.tokens), _ref(params, g1.prompt, 14))
    assert np.array_equal(np.array(g2.tokens), _ref(params, g2.prompt, 6))
    st = eng.stats()
    assert 0 < st["lookahead_steps"] < st["executor"]["decode_steps"]
    assert st["lookahead_discarded"] == 0


def test_drain_reads_the_launch_left_in_flight(params):
    """A request of two tokens is launched whole in its first step and
    its row is given back with its last token in flight: nothing is
    queued, live or prefilling, and there is still work."""
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=4, max_len=64)
    req = eng.submit(np.array([5, 17, 3], np.int32), max_new_tokens=2)
    assert eng.step() == []
    assert not (eng.queue or eng.active or eng.prefilling)
    assert eng.cache.allocator.used == 0 and req.tokens == []
    assert eng.has_work and eng.stats()["executor"]["decode_steps"] == 1
    events = eng.drain()
    assert [e.tokens for e in events] == [[t] for t in req.tokens]
    assert events[-1].done and not eng.has_work and eng._ahead is None
    assert np.array_equal(np.array(req.tokens),
                          _ref(params, req.prompt, 2))


def test_static_batching_runs_to_completion(params):
    """static mode: nothing is admitted while a batch is in flight; the
    tokens still match generate()."""
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=2, max_len=64, static_batching=True)
    reqs = [eng.submit(np.array([7 * (i + 1)], np.int32),
                       max_new_tokens=4) for i in range(3)]
    eng.step()                       # admits exactly max_batch
    assert len(eng.active) == 2 and len(eng.queue) == 1
    eng.step()
    assert len(eng.queue) == 1       # no top-up mid-batch
    eng.drain()
    for r in reqs:
        assert np.array_equal(np.array(r.tokens),
                              _ref(params, r.prompt, 4))


# -- store integration -------------------------------------------------------

def test_store_hot_swap_adopts_new_weights(params):
    """tensor_llm's executor rides the model-store epoch contract: after
    update(), the next step serves the new version's weights."""
    reset_store()
    try:
        store = get_store()
        from nnstreamer_tpu.backends.xla import ModelBundle

        p2 = init_params(vocab=61, d_model=32, n_layers=2, n_heads=4,
                         n_kv_heads=2, seed=9)
        store.register("llm_swap_t", ModelBundle(fn=None, params=params))
        eng = LLMEngine("store://llm_swap_t", n_heads=4, block_size=4,
                        num_blocks=32, max_batch=4, max_len=64)
        prompt = np.array([3, 44, 8], np.int32)
        r1 = eng.submit(prompt, max_new_tokens=5)
        eng.drain()
        assert np.array_equal(np.array(r1.tokens), _ref(params, prompt, 5))
        store.register("llm_swap_t", ModelBundle(fn=None, params=p2))
        store.update("llm_swap_t")
        r2 = eng.submit(prompt, max_new_tokens=5)
        eng.drain()
        assert eng.executor.swap_count == 1
        assert np.array_equal(np.array(r2.tokens), _ref(p2, prompt, 5))
    finally:
        reset_store()


def _swap_with_a_request_in_flight(params, name):
    """Two steps of one request, a swap, one more step, then the rest:
    (the engine and the request after that step, the labels of the
    step's waits, swap and launches in order, the whole stream)."""
    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.runtime.tracing import Tracer

    store = get_store()
    p2 = init_params(vocab=61, d_model=32, n_layers=2, n_heads=4,
                     n_kv_heads=2, seed=9)
    store.register(name, ModelBundle(fn=None, params=params))
    tr = Tracer()
    eng = LLMEngine(f"store://{name}", n_heads=4, block_size=4,
                    num_blocks=32, max_batch=4, max_len=64, tracer=tr,
                    name="e")
    req = eng.submit(np.array([3, 44, 8], np.int32), max_new_tokens=7)
    eng.step()
    eng.step()
    store.register(name, ModelBundle(fn=None, params=p2))
    store.update(name)
    mark = len(tr.events())
    eng.step()
    assert eng.executor.swap_count == 1
    labels = [ev[3] for ev in tr.events()[mark:]
              if ev[3] in ("wait", "model_swap", "dispatch")]
    seen = (len(req.tokens), req.ahead)
    eng.drain()
    return labels, seen, list(req.tokens)


def test_hot_swap_reads_the_launch_in_flight_first(params, monkeypatch):
    """A swap that lands with a launch unread: the step that adopts it
    reads that launch (the old version's last) before it adopts and
    launches, so a request in flight changes version at the token the
    resolved order changes it at."""
    reset_store()
    try:
        labels, seen, ahead = _swap_with_a_request_in_flight(
            params, "llm_swap_a")
        assert labels == ["wait", "model_swap", "dispatch"]
        assert seen == (3, 1)       # three read, the new version's first out
        monkeypatch.setattr(LLMEngine, "_runs_ahead",
                            lambda self, pending: False)
        labels, seen, resolved = _swap_with_a_request_in_flight(
            params, "llm_swap_r")
        assert labels == ["model_swap", "dispatch", "wait"]
        assert seen == (4, 0)
        # three tokens of the old version, then the new one's over the
        # old one's KV: the same stream in both orders
        assert ahead[:3] == [int(t) for t in _ref(params, [3, 44, 8], 3)]
        assert ahead == resolved and len(ahead) == 7
    finally:
        reset_store()


def test_tracer_records_llm_requests(params):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=4, max_len=64, tracer=tr, name="e")
    eng.submit(np.array([1, 2], np.int32), max_new_tokens=3)
    eng.drain()
    recs = tr.llm_requests()
    assert len(recs) == 1
    name, req_id, t, args = recs[0]
    assert name == "e" and args["n_tokens"] == 3
    assert args["first_token_ms"] is not None
    assert tr.summary()["llm_requests"] == 1


# -- tensor_llm element (tier-1 smoke) ---------------------------------------

def _llm_pipeline(params, **llm_props):
    reset_store()
    from nnstreamer_tpu.backends.xla import ModelBundle

    get_store().register("llm_el_t", ModelBundle(fn=None, params=params))
    src = AppSrc(name="src", spec=TensorsSpec(
        tensors=(), format=TensorFormat.FLEXIBLE))
    llm = TensorLLM(name="llm", model="store://llm_el_t", block_size=4,
                    num_blocks=32, max_batch=4, max_len=64, **llm_props)
    sink = TensorSink(name="sink")
    pipe = nns.Pipeline()
    for e in (src, llm, sink):
        pipe.add(e)
    pipe.link(src, llm)
    pipe.link(llm, sink)
    return pipe, src, llm, sink


def test_tensor_llm_smoke_concurrent_requests(params):
    """Tier-1 smoke: 4 concurrent requests through the element; every
    request terminates with exactly its token budget, streamed
    incrementally, matching generate()."""
    budgets = {"r0": 3, "r1": 6, "r2": 2, "r3": 5}
    pipe, src, llm, sink = _llm_pipeline(params)
    runner = nns.PipelineRunner(pipe)
    runner.start()
    try:
        rng = np.random.default_rng(11)
        prompts = {}
        for rid, budget in budgets.items():
            p = rng.integers(0, 61, size=int(rng.integers(1, 9))) \
                .astype(np.int32)
            prompts[rid] = p
            src.push(TensorBuffer(
                tensors=(p,), pts=0,
                meta={"llm": {"request_id": rid,
                              "max_new_tokens": budget}}))
        src.end()
        runner.wait(120)
    finally:
        runner.stop()
    got = {}
    finals = {}
    for b in sink.results:
        m = b.meta["llm"]
        got.setdefault(m["request_id"], []).extend(
            int(t) for t in np.asarray(b.tensors[0]))
        if m["done"]:
            finals[m["request_id"]] = m
    assert set(got) == set(budgets)
    for rid, budget in budgets.items():
        assert len(got[rid]) == budget, rid
        assert finals[rid]["finish_reason"] == "length"
        assert np.array_equal(np.array(got[rid]),
                              _ref(params, prompts[rid], budget))
    stats = llm.extra_stats()
    assert stats["finished"] == 4
    assert stats["cache"]["blocks_used"] == 0
    reset_store()


def test_tensor_llm_element_properties_registered():
    from nnstreamer_tpu.core.registry import PluginKind, registry

    cls = registry.get(PluginKind.ELEMENT, "tensor_llm")
    assert cls is TensorLLM
    for prop in ("model", "scheduling", "block_size", "num_blocks",
                 "max_batch", "max_new_tokens", "admit_window_ms",
                 "paged_kernel", "prefill_chunk"):
        assert prop in cls.PROPS


def test_tensor_llm_pallas_chunked_matches_generate(params):
    """Element-level twin of the smoke test with the Pallas kernel and
    chunked prefill enabled: tokens are identical to generate() and the
    executor reports pallas invokes with no fallback."""
    budgets = {"p0": 4, "p1": 3}
    pipe, src, llm, sink = _llm_pipeline(
        params, paged_kernel="pallas", prefill_chunk=4)
    runner = nns.PipelineRunner(pipe)
    runner.start()
    try:
        rng = np.random.default_rng(23)
        prompts = {}
        for rid, budget in budgets.items():
            p = rng.integers(0, 61, size=int(rng.integers(5, 12))) \
                .astype(np.int32)
            prompts[rid] = p
            src.push(TensorBuffer(
                tensors=(p,), pts=0,
                meta={"llm": {"request_id": rid,
                              "max_new_tokens": budget}}))
        src.end()
        runner.wait(120)
    finally:
        runner.stop()
    got = {}
    for b in sink.results:
        m = b.meta["llm"]
        got.setdefault(m["request_id"], []).extend(
            int(t) for t in np.asarray(b.tensors[0]))
    for rid, budget in budgets.items():
        assert np.array_equal(np.array(got[rid]),
                              _ref(params, prompts[rid], budget)), rid
    stats = llm.extra_stats()
    ex = stats["executor"]
    assert ex["paged_kernel"] == "pallas"
    assert ex["kernel_invokes"]["pallas"] > 0
    assert ex["kernel_invokes"]["xla"] == 0
    reset_store()


@pytest.mark.slow
def test_tensor_llm_open_loop_arrivals(params):
    """Open-loop Poisson arrivals through the element (the llm_serve
    bench family's shape, scaled down): every request completes and
    continuous batching keeps the pool bounded."""
    pipe, src, llm, sink = _llm_pipeline(params, prewarm=8)
    runner = nns.PipelineRunner(pipe)
    runner.start()
    try:
        rng = np.random.default_rng(5)
        arrivals = np.cumsum(rng.exponential(0.01, size=10))
        t0 = time.perf_counter()
        for i, t_arr in enumerate(arrivals):
            dt = t_arr - (time.perf_counter() - t0)
            if dt > 0:
                time.sleep(dt)
            src.push(TensorBuffer(
                tensors=(rng.integers(0, 61, size=3).astype(np.int32),),
                pts=i, meta={"llm": {"request_id": f"q{i}",
                                     "max_new_tokens": 4}}))
        src.end()
        runner.wait(120)
    finally:
        runner.stop()
    done = [b.meta["llm"] for b in sink.results if b.meta["llm"]["done"]]
    assert len(done) == 10
    stats = llm.extra_stats()
    assert stats["cache"]["blocks_high_water"] <= \
        stats["cache"]["blocks_total"]
    reset_store()


# -- the serving thread's own spans (docs/observability.md) ------------------

def _spans(tr, cat=None, prefix=""):
    """The tracer's "X" spans as (label, t0, t1, args), in time order."""
    return sorted(
        ((label, ts, ts + dur, args or {})
         for ph, c, _n, label, ts, dur, args in tr.events()
         if ph == "X" and (cat is None or c == cat)
         and label.startswith(prefix)), key=lambda s: s[1])


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def _serve_traced(params, requests, tracer, **llm_props):
    """Push `requests` ({rid: (prompt, budget)}) through the element and
    end the stream only after every one is answered, so that the engine
    steps on the timer path, as it does when serving."""
    pipe, src, llm, sink = _llm_pipeline(params, **llm_props)
    runner = nns.PipelineRunner(pipe, trace=tracer)
    runner.start()
    try:
        for rid, (prompt, budget) in requests.items():
            src.push(TensorBuffer(
                tensors=(np.asarray(prompt, np.int32),), pts=0,
                meta={"llm": {"request_id": rid,
                              "max_new_tokens": budget}}))
        deadline = time.perf_counter() + 120
        while llm.extra_stats().get("finished", 0) < len(requests):
            assert time.perf_counter() < deadline, "requests unanswered"
            time.sleep(0.01)
        src.end()
        runner.wait(60)
    finally:
        runner.stop()
    reset_store()
    return sink


def test_step_spans_nest_and_order_on_the_serving_thread(params):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    _serve_traced(params, {"a": ([1, 2, 3], 5), "b": ([4, 5], 3)}, tr)
    timers = _spans(tr, "element", "timer")
    backend = _spans(tr, "backend")
    steps = [t for t in timers
             if any(_inside(b, t) for b in backend)]
    assert len(steps) >= 4
    admits = _spans(tr, "llm", "admit")
    for t in steps:
        # exactly one admission a step, and its label is an outcome
        mine = [a for a in admits if _inside(a, t)]
        assert len(mine) == 1, (t, mine)
        assert mine[0][0] in ("admit", "admit_none_queued",
                              "admit_blocked", "admit_full")
        assert "input_depth" in t[3]
    assert all(any(_inside(a, t) for t in steps) for a in admits)

    def step_of(span):
        mine = [i for i, t in enumerate(steps) if _inside(span, t)]
        assert len(mine) == 1, span
        return mine[0]

    def decode(label):
        return [b for b in backend if b[0] == label
                and b[3].get("what") == "llm_decode"]

    # a launch: prep then dispatch inside one step, nothing waited for
    launches = decode("dispatch")
    assert len(launches) >= 4
    for d in launches:
        prep = [b for b in decode("prep") if b[2] == d[1]]
        assert len(prep) == 1 and step_of(prep[0]) == step_of(d)
        assert "kernel" not in d[3] and "kernel" not in prep[0][3]
    # a read: wait then readback of a few ids, after the step's own
    # launch where it made one, and one sample span behind it
    reads = decode("wait")
    samples = _spans(tr, "llm", "sample")
    assert len(reads) == len(samples)
    for w in reads:
        back = [b for b in decode("readback") if b[1] == w[2]]
        assert len(back) == 1 and step_of(back[0]) == step_of(w)
        assert 0 < back[0][3]["bytes"] <= 4 * 8     # ids, never logits
        ahead = [d for d in launches if step_of(d) == step_of(w)]
        assert len(ahead) <= 1 and all(d[2] <= w[1] for d in ahead)
        mine = [s for s in samples if back[0][2] <= s[1]
                and step_of(s) == step_of(w)]
        assert len(mine) == 1
    # a launch's invoke (compile, a first call) span is its own: from
    # its dispatch to the end of the wait that read it, a step later
    outers = [b for b in backend if b[0] in ("invoke", "compile")
              and b[3]["what"] == "llm_decode"]
    assert len(outers) == len(launches)
    for o in outers:
        d = [d for d in launches if d[1] == o[1]]
        assert len(d) == 1
        if o[0] == "compile":
            assert _inside(d[0], o) and step_of(o) == step_of(d[0])
            continue
        assert o[3]["rows"] >= 1 and o[3]["kv_tokens"] >= o[3]["rows"]
        w = [w for w in reads if w[2] == o[2]]
        assert len(w) == 1 and step_of(w[0]) == step_of(d[0]) + 1
    # the children are not counted as kernel spans a second time
    assert sum(tr.kernel_spans().values()) == len(
        [b for b in backend if b[0] in ("invoke", "compile")
         and "kernel" in b[3]])
    # emission is timed from on_timer's return, inside the same timer
    # span
    emits = _spans(tr, "element", "emit")
    assert emits
    for e in emits:
        assert e[3]["n"] >= 1 and any(_inside(e, t) for t in steps)
    # every event of the ring is on the one clock
    t_lo, t_hi = timers[0][1] - 60, timers[-1][2] + 60
    assert all(t_lo < ev[4] < t_hi for ev in tr.events())


def test_request_spans_share_an_identifier(params):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    _serve_traced(params, {"r7": ([9, 8, 7, 6], 3)}, tr)
    queued = [s for s in _spans(tr, "llm", "queued")]
    assert len(queued) == 1 and queued[0][3] == {"req": "r7"}
    prefill = [b for b in _spans(tr, "backend")
               if b[0] in ("invoke", "compile")
               and b[3]["what"] == "llm_prefill"]
    assert len(prefill) == 1
    inst = {label: args for ph, _c, _n, label, _t, _d, args in tr.events()
            if ph == "i" and label in ("first_token", "llm_request")}
    assert inst["first_token"]["req"] == "r7"
    assert inst["llm_request"]["req_id"] == "r7"
    # queued ends at admission, where the prefill starts
    assert queued[0][2] <= prefill[0][1]
    if prefill[0][0] == "invoke":
        assert prefill[0][3]["req"] == "r7"


@pytest.mark.parametrize("label", ["admit", "admit_none_queued",
                                   "admit_blocked", "admit_full"])
def test_admit_label_is_the_outcome(params, label):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    rows = 16 if label == "admit_full" else 4
    # 7 usable blocks of 4 slots: one request of 3 + 17 takes 5
    blocks = 8 if label == "admit_blocked" else 96
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=blocks,
                    max_batch=rows, max_len=64, tracer=tr, name="e")
    n = {"admit": 1, "admit_none_queued": 1, "admit_blocked": 2,
         "admit_full": 17}[label]
    for _ in range(n):
        eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=17)
    eng.step()
    first = _spans(tr, "llm", "admit")[-1]
    want_first = {"admit": 1, "admit_none_queued": 1, "admit_blocked": 1,
                  "admit_full": 16}[label]
    assert first[0] == "admit" and first[3]["admitted"] == want_first
    assert first[3]["rows"] == 0 and first[3]["queued"] == n
    eng.step()
    second = _spans(tr, "llm", "admit")[-1]
    if label == "admit":
        return
    assert second[0] == label and second[3]["admitted"] == 0
    assert second[3]["step"] == 1 and second[3]["rows"] == want_first
    assert second[3]["queued"] == n - want_first
    # as admission left the pool: each row then held its prompt's one
    # block, and the launch after it grew each row a second
    assert second[3]["blocks_free"] == \
        eng.cache.allocator.total - want_first
    assert eng.cache.allocator.free == \
        eng.cache.allocator.total - 2 * want_first
    if label == "admit_blocked":
        assert eng.admission_blocked >= 1
    if label == "admit_full":
        assert len(eng.active) == 16 == eng.max_batch


def test_decode_invoke_carries_the_context_it_attends(params):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    eng = LLMEngine(params, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=4, max_len=64, tracer=tr, name="e")
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    eng.step()                      # compiles; its span is `compile`
    want = []
    for _ in range(3):
        live = [r for r in eng.active if r.state == "active"]
        want.append(sum(r.pos for r in live) + len(live))
        eng.step()
    eng.step()                      # reads the third launch
    got = [b[3]["kv_tokens"] for b in _spans(tr, "backend", "invoke")
           if b[3]["what"] == "llm_decode"]
    # prompts of 5 and 8 and one decoded token each before the first
    # `invoke`: (6 + 9) held + 2 written, then two more a step; the
    # fourth step's launch is not read yet and has no span
    assert got == want == [17, 19, 21]
    eng.executor.close()


def test_first_call_children_lie_inside_the_compile_span():
    """jax's own duration events of a first call become children of its
    `compile` span, the outermost of each label only."""
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    # widths no other test uses, so that jax has nothing traced for them
    fresh = init_params(vocab=59, d_model=40, n_layers=1, n_heads=4,
                        n_kv_heads=2, seed=1)
    eng = LLMEngine(fresh, n_heads=4, block_size=4, num_blocks=32,
                    max_batch=2, max_len=64, tracer=tr, name="fc")
    eng.submit(np.array([5, 4, 3], np.int32), max_new_tokens=2)
    eng.drain()
    eng.executor.close()
    eng.executor.close()            # closing twice is harmless
    backend = _spans(tr, "backend")
    compiles = [b for b in backend if b[0] == "compile"]
    kids = [b for b in backend if b[0].startswith("jax_")]
    assert len(compiles) == 2       # one prefill, one decode bucket
    for c in compiles:
        mine = [k for k in kids if _inside(k, c)]
        assert {k[0] for k in mine} >= {"jax_trace", "jax_lower",
                                        "jax_backend_compile"}
        assert len(mine) <= 8       # not one a nested jit
    assert all(any(_inside(k, c) for c in compiles) for k in kids)


class _RaisingTracer:
    """Inactive, and any hook called on it is a test failure: with
    tracing off a guarded site may load `.active` and nothing else."""
    active = False

    def __getattr__(self, name):
        raise AssertionError(f"tracer.{name} touched with tracing off")


def test_tracing_off_touches_no_tracer_hook(params):
    sink = _serve_traced(params, {"x": ([3, 1, 4, 1, 5], 6),
                                  "y": ([2, 7], 4)}, _RaisingTracer())
    done = {b.meta["llm"]["request_id"]: b.meta["llm"]["n_tokens"]
            for b in sink.results if b.meta["llm"]["done"]}
    assert done == {"x": 6, "y": 4}
