"""The engine's account of every row of every decode launch and of every
stage of a request (llm/engine.py, ISSUE 37): counts only, on the CPU at
tiny sizes. Two identities hold exactly after any served backlog: the
states sum to `total`, and `total` is `max_batch` x the executor's
`decode_steps`. Each cause a free row can stand under is reached once by
construction and its row-steps counted by hand."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_hybrid                                              # noqa: E402
import tiny_sparse_moe                                          # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.llm.engine import (                         # noqa: E402
    ROW_STATES, STAGE_WINDOW, STAGES, LLMEngine, LLMRequest)
from nnstreamer_tpu.llm.paged_cache import BlockAllocator       # noqa: E402
from nnstreamer_tpu.models.transformer import init_params       # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import hybrid_lm as hybrid_ref        # noqa: E402
from perfbench.references import sparse_moe_lm as moe_ref       # noqa: E402
from perfbench.runners.hybrid_llm import lm_spec as hybrid_spec  # noqa: E402
from perfbench.runners.sparse_moe_llm import lm_spec as moe_spec  # noqa: E402

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def dense():
    return init_params(vocab=61, d_model=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, seed=0)


@pytest.fixture(scope="module")
def hybrid():
    cfg = tiny_hybrid.CONFIG
    return ModelBundle(
        fn=None, lm=hybrid_spec(cfg),
        params=hybrid_ref.make_params(cfg, SEED, dtype=jnp.float32))


@pytest.fixture(scope="module")
def sparse_moe():
    cfg = tiny_sparse_moe.CONFIG
    return ModelBundle(
        fn=None, lm=moe_spec(cfg),
        params=moe_ref.make_params(cfg, SEED, dtype=jnp.float32))


def _dense_engine(params, **kw):
    given = dict(n_heads=4, block_size=4, num_blocks=64, max_batch=4,
                 max_len=64)
    return LLMEngine(params, **dict(given, **kw))


def _hybrid_engine(bundle, **kw):
    given = dict(dtype=jnp.float32, max_batch=2, prefill_chunk=8,
                 block_size=4, num_blocks=80, max_len=64)
    return LLMEngine(bundle, **dict(given, **kw))


def _prompt(n, seed=0, vocab=61):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _check_identities(eng):
    st = eng.stats()
    rows, ex = st["rows"], st["executor"]
    assert set(rows) == set(ROW_STATES) | {"total"}
    assert all(isinstance(v, int) and v >= 0 for v in rows.values()), rows
    assert sum(rows[k] for k in ROW_STATES) == rows["total"], rows
    assert rows["total"] == eng.max_batch * ex["decode_steps"], rows
    # every decode token is one row of one launch; a request's first
    # token is its prefill's, and a row that stopped on its eos_id a
    # launch earlier decoded one token that was never delivered
    assert rows["decode"] == (st["tokens_out"] - st["finished"]
                              + st["lookahead_discarded"]), st
    return rows


# -- the two identities, a served backlog in each family's engine ------------

def _backlog(eng, lengths, budgets, vocab=61, **kw):
    reqs = [eng.submit(_prompt(n, seed=i, vocab=vocab), max_new_tokens=b,
                       **kw)
            for i, (n, b) in enumerate(zip(lengths, budgets))]
    eng.drain()
    assert all(len(r.tokens) == r.max_new_tokens or r.finish_reason == "eos"
               for r in reqs)
    return reqs


DENSE_CASES = {
    "unchunked": {},
    "small_pool": {"num_blocks": 12, "max_len": 32},
    "chunk_every_1": {"prefill_chunk": 8, "chunk_every": 1},
    "chunk_every_4": {"prefill_chunk": 8, "chunk_every": 4},
    "static": {"static_batching": True},
}


@pytest.mark.parametrize("case", sorted(DENSE_CASES))
def test_identities_hold_after_a_dense_backlog(dense, case):
    eng = _dense_engine(dense, **DENSE_CASES[case])
    _backlog(eng, (3, 21, 5, 1, 17, 9, 2), (7, 3, 1, 9, 4, 6, 2))
    rows = _check_identities(eng)
    assert rows["decode"] > 0
    if case != "static":
        assert rows["other"] == 0
    if not case.startswith("chunk"):
        assert rows["prefilling"] == eng.chunk_deferred_steps == 0
    if case == "small_pool":
        assert rows["blocked"] > 0


def test_identities_hold_in_the_resolved_order_and_with_an_eos(dense):
    """A sampled row makes every step read before it launches: a row
    that ends there between admission and launch stood held at the
    one and is not carried by the other (`retiring`). A greedy row that
    stops on its eos_id a launch late decoded one row-step more than it
    delivered."""
    eng = _dense_engine(dense)
    _backlog(eng, (3, 7, 5, 2, 9, 4), (6, 2, 1, 8, 3, 5), temperature=0.8,
             top_k=8)
    rows = _check_identities(eng)
    assert eng.lookahead_steps == 0 and rows["decode"] > 0
    probe = eng.submit(_prompt(4, seed=3), max_new_tokens=8)
    eng.drain()
    eos = probe.tokens[2]
    if eos in probe.tokens[:2]:
        pytest.skip("the probe repeats its third token earlier")
    eng = _dense_engine(dense)
    eng.submit(_prompt(4, seed=3), max_new_tokens=8, eos_id=eos)
    eng.submit(_prompt(6, seed=4), max_new_tokens=9)
    eng.drain()
    _check_identities(eng)
    assert eng.lookahead_discarded == 1


@pytest.mark.parametrize("every", [1, 4])
def test_identities_hold_with_a_state_pool(hybrid, every):
    eng = _hybrid_engine(hybrid, max_batch=3, chunk_every=every)
    _backlog(eng, (21, 6, 33, 5, 17), (5, 7, 4, 9, 3), vocab=256)
    rows = _check_identities(eng)
    assert rows["prefilling"] > 0 and rows["other"] == 0
    assert (eng.chunk_deferred_steps > 0) == (every == 4)
    assert eng.cache.stats()["state_slots_used"] == 0


def test_identities_hold_in_the_sparse_expert_family(sparse_moe):
    eng = LLMEngine(sparse_moe, dtype=jnp.float32, block_size=8,
                    num_blocks=40, max_len=64, max_batch=2,
                    prefill_chunk=8, chunk_every=2)
    _backlog(eng, (5, 29, 13, 3), (6, 3, 4, 5),
             vocab=int(tiny_sparse_moe.CONFIG["vocab_size"]))
    rows = _check_identities(eng)
    assert rows["prefilling"] > 0 and rows["other"] == 0


# -- each cause once, its row-steps counted by hand ---------------------------

def _only(rows, **want):
    want.setdefault("total", sum(want.values()))
    assert {k: v for k, v in rows.items() if v} == want, rows


def test_a_pool_too_small_reads_blocked(dense):
    """Seven usable blocks, two a request at its longest: three live of
    four rows, the fourth free behind a queue's head whose admission
    would bring the peak to eight. At the fourth step the three have two
    launches left and the head needs one block until they are gone
    (peak seven): it is admitted, three steps blocked. The first three
    end with the fifth launch; the last two requests are admitted into
    what they give back, and rows stand free with nothing queued in the
    launches that carry three requests (three) and two (two)."""
    eng = _dense_engine(dense, num_blocks=8, max_len=16)
    for i in range(6):
        eng.submit(np.array([i + 1, i + 2], np.int32), max_new_tokens=6)
    eng.drain()
    _only(_check_identities(eng), decode=30, blocked=3, unfed=7)
    assert eng.executor.stats()["decode_steps"] == 10
    st = eng.stats()["cache"]
    assert st["admit_peak_blocks"] == st["blocks_live_high_water"] == 7
    # a request of 2 + 6 is admitted with one block and grows one
    assert st["blocks_grown"] == 6


def test_one_state_slot_short_reads_blocked_state(hybrid):
    """Two rows and one state slot: the second request waits for the
    slot through the first one's three launches, then decodes alone."""
    eng = _hybrid_engine(hybrid)
    eng.cache.state_alloc = BlockAllocator(2)       # the scratch and one
    for i in range(2):
        eng.submit(_prompt(5, seed=i, vocab=256), max_new_tokens=4)
    eng.drain()
    _only(_check_identities(eng), decode=6, blocked_state=3, unfed=3)


def test_an_empty_queue_reads_unfed(dense):
    eng = _dense_engine(dense)
    eng.submit(_prompt(3), max_new_tokens=5)
    eng.drain()
    _only(_check_identities(eng), decode=4, unfed=12)


def test_a_static_batch_still_running_reads_other(dense):
    """`static_batching`: a request that arrives while a batch runs
    waits for it to end, beside three free rows."""
    eng = _dense_engine(dense, static_batching=True)
    eng.submit(_prompt(3), max_new_tokens=4)
    eng.step()                          # launch 1: a alone, nothing queued
    eng.submit(_prompt(2, seed=1), max_new_tokens=2)
    eng.drain()                         # launches 2, 3: b waits; 4: b
    _only(_check_identities(eng), decode=4, unfed=6, other=6)


def test_a_last_token_in_flight_reads_retiring(dense):
    """A request of one token holds its row at the step that admits it
    and is in no launch: its only token is its prefill's."""
    eng = _dense_engine(dense, max_batch=2)
    eng.submit(_prompt(3), max_new_tokens=4)
    eng.submit(_prompt(2, seed=1), max_new_tokens=1)
    eng.drain()
    _only(_check_identities(eng), decode=3, retiring=1, unfed=2)


@pytest.mark.parametrize("every, joins_at, deferred", [(1, 2, 0), (4, 8, 6)])
def test_a_prompt_in_chunks_holds_its_row(dense, every, joins_at, deferred):
    """Two rows; a short prompt decodes 11 tokens in the launches of
    steps 0-10 while a prompt of 20 passes in three chunks of 8. With no
    row live yet its first chunk rides step 0; the others ride every
    step, or every fourth (steps 4 and 8, six steps deferred). Until
    its last chunk it holds a row and decodes nothing; it then decodes
    in two launches, and the short one's last launch leaves a row free
    with nothing queued."""
    eng = _dense_engine(dense, max_batch=2, prefill_chunk=8,
                        chunk_every=every)
    a = eng.submit(_prompt(3), max_new_tokens=12)
    b = eng.submit(_prompt(20, seed=1), max_new_tokens=3)
    eng.drain()
    assert len(a.tokens) == 12 and len(b.tokens) == 3
    rows = _check_identities(eng)
    _only(rows, decode=13, prefilling=joins_at, unfed=9 - joins_at)
    assert rows["total"] == 2 * 11
    st = eng.stats()
    assert st["chunk_deferred_steps"] == deferred
    assert st["executor"]["chunk_prefills"] == 3


# -- a request's stages --------------------------------------------------------

def test_stages_are_ordered_and_sum_to_the_first_token(dense):
    eng = _dense_engine(dense, max_batch=2, prefill_chunk=8, chunk_every=2)
    a = eng.submit(_prompt(19), max_new_tokens=6)
    b = eng.submit(_prompt(20, seed=1), max_new_tokens=3)
    c = eng.submit(_prompt(4, seed=2), max_new_tokens=2)    # waits for a row
    eng.drain()
    for r in (a, b, c):
        assert r.t_submit <= r.t_admit <= r.t_prefill0 <= r.t_first \
            <= r.t_last
    # b's first chunk waits behind a's three; c is launched where admitted
    assert b.t_prefill0 > b.t_admit and c.t_prefill0 == c.t_admit
    assert c.t_admit > a.t_first
    st = eng.stats()
    for key in STAGES:
        assert set(st[key]) == {"p50", "p95", "p99"}
        assert st[key]["p50"] <= st[key]["p95"] <= st[key]["p99"]
    one = _dense_engine(dense, prefill_chunk=8)
    one.submit(_prompt(20), max_new_tokens=3)
    one.drain()
    st = one.stats()
    parts = sum(st[k]["p50"] for k in ("queued_ms", "prefill_wait_ms",
                                       "prefill_ms"))
    assert parts == pytest.approx(st["first_token_ms"]["p50"], abs=0.002)
    assert len(one._stage["inter_token_ms"]) == 2


def test_no_container_outgrows_its_bound(dense):
    """20,000 tokens recorded over 200 requests: the engine keeps the
    latest `STAGE_WINDOW` samples a stage and no list a token."""
    eng = _dense_engine(dense)
    for i in range(200):
        req = LLMRequest(req_id=f"r{i}", prompt=_prompt(2),
                         max_new_tokens=100)
        for _ in range(100):
            eng._record_token(req, 7)
    assert eng.tokens_out == 20000
    sized = {k: len(v) for k, v in vars(eng).items() if hasattr(v, "__len__")}
    assert max(sized.values()) <= len(ROW_STATES) + 1, sized
    assert {k: len(v) for k, v in eng._stage.items()} == {
        "queued_ms": 200, "prefill_wait_ms": 200, "prefill_ms": 200,
        "first_token_ms": 200, "inter_token_ms": STAGE_WINDOW}
    st = eng.stats()
    assert all(set(st[k]) == {"p50", "p95", "p99"} for k in STAGES)

    def longest(obj):
        if isinstance(obj, dict):
            return max([len(obj)] + [longest(v) for v in obj.values()])
        return len(obj) if isinstance(obj, (list, tuple)) else 0

    assert longest(st) < 64


# -- the same account in the ring ---------------------------------------------

def _rows_events(tr, name):
    return [args for ph, cat, n, label, _t, _d, args in tr.events()
            if (ph, cat, n, label) == ("C", "llm", name, "rows")]


def test_one_rows_event_a_launch_says_what_the_counters_say(hybrid):
    tr = Tracer()
    eng = _hybrid_engine(hybrid, max_batch=3, chunk_every=2, tracer=tr,
                         name="h")
    eng.cache.state_alloc = BlockAllocator(3)       # two slots, three rows
    for i, (n, b) in enumerate(((21, 5), (6, 7), (13, 4), (5, 3))):
        eng.submit(_prompt(n, seed=i, vocab=256), max_new_tokens=b)
    seen = 0
    while eng.has_work:
        before = dict(eng.rows)
        launches = eng.executor.decode_steps
        eng.step()
        events = _rows_events(tr, "h")
        assert len(events) == eng.executor.decode_steps
        if eng.executor.decode_steps == launches:
            assert eng.rows == before
            continue
        ev = events[-1]
        delta = {k: eng.rows[k] - before[k] for k in before}
        vals = ev["values"]
        assert list(vals) == ["decode", "prefilling", "retiring", "free"]
        assert sum(vals.values()) == delta["total"] == 3
        for state in ("decode", "prefilling", "retiring"):
            assert vals[state] == delta[state]
        assert ev["step"] == eng.steps - 1 and ev["queued"] >= 0
        if vals["free"]:
            assert delta[ev["cause"]] == vals["free"]
        else:
            assert ev["cause"] is None
        seen += 1
    rows = _check_identities(eng)
    assert seen == eng.executor.decode_steps > 0
    assert rows["blocked_state"] > 0 and rows["prefilling"] > 0
    # the admit span says how many of its rows were prefilling
    admits = [args for ph, cat, _n, label, _t, _d, args in tr.events()
              if ph == "X" and cat == "llm" and label.startswith("admit")]
    assert admits and all(0 <= a["prefilling"] <= a["rows"] for a in admits)
    assert any(a["prefilling"] for a in admits)


def test_a_request_reads_as_one_chain_under_one_req(dense):
    tr = Tracer()
    eng = _dense_engine(dense, max_batch=2, prefill_chunk=8, chunk_every=2,
                        tracer=tr, name="e")
    reqs = [eng.submit(_prompt(19), max_new_tokens=6, req_id="a"),
            eng.submit(_prompt(20, seed=1), max_new_tokens=3, req_id="b"),
            eng.submit(_prompt(4, seed=2), max_new_tokens=2, req_id="c")]
    eng.drain()
    chain = ("queued", "prefill_wait", "prefill", "first_token",
             "llm_request")
    by_req = {r.req_id: [] for r in reqs}
    for ph, _c, _n, label, ts, dur, args in tr.events():
        if label in chain:
            by_req[args["req"]].append((label, ph, ts, dur, args))
    for r in reqs:
        # one event of each kind, in the chain's order
        assert tuple(ev[0] for ev in by_req[r.req_id]) == chain
        mine = {ev[0]: ev[1:] for ev in by_req[r.req_id]}
        q, w, p = (mine[k] for k in chain[:3])
        assert q[0] == w[0] == p[0] == "X"
        # each stage starts where the one before ended, on one clock
        assert q[1] == r.t_submit and q[1] + q[2] == pytest.approx(w[1])
        assert w[1] == r.t_admit and w[1] + w[2] == pytest.approx(p[1])
        assert p[1] == r.t_prefill0
        assert p[1] + p[2] == pytest.approx(mine["first_token"][1])
        assert mine["first_token"][1] == r.t_first
        assert mine["llm_request"][1] >= r.t_last
    args = {rid: {ev[0]: ev[4] for ev in evs}
            for rid, evs in by_req.items()}
    # a's first chunk rides the step that admits it; b's waits for a's
    # three (steps 0-2: with no row live a chunk rides every step), is
    # held back at step 3 (a decodes: every second step) and rides step
    # 4; c is no chunk at all
    assert args["a"]["prefill_wait"] == {"req": "a", "steps": 0, "chunks": 0}
    assert args["b"]["prefill_wait"] == {"req": "b", "steps": 4, "chunks": 3}
    assert args["a"]["prefill"]["chunks"] == 3 == args["b"]["prefill"][
        "chunks"]
    assert args["c"]["prefill"]["chunks"] == 1
    assert args["c"]["prefill_wait"]["steps"] == 0
    assert all(args[r]["prefill"]["steps"] >= 1 for r in "abc")


def test_the_chrome_trace_has_the_rows_track(dense):
    import json

    tr = Tracer()
    eng = _dense_engine(dense, max_batch=2, prefill_chunk=8, tracer=tr,
                        name="e")
    eng.submit(_prompt(3), max_new_tokens=5)
    eng.submit(_prompt(20, seed=1), max_new_tokens=3)
    tr.enqueue("e", 2, 1.0)             # a queue-depth sample keeps its form
    eng.drain()
    doc = json.loads(json.dumps(tr.to_chrome_trace("t")))
    track = [ev for ev in doc["traceEvents"]
             if ev["ph"] == "C" and ev["name"] == "rows:e"]
    assert len(track) == eng.executor.decode_steps > 0
    for ev in track:
        assert set(ev["args"]) == {"decode", "prefilling", "retiring",
                                   "free"}
        assert sum(ev["args"].values()) == 2 and ev["cat"] == "llm"
    assert [ev["ts"] for ev in track] == sorted(ev["ts"] for ev in track)
    assert sum(ev["args"]["decode"] for ev in track) == eng.rows["decode"]
    depth = [ev for ev in doc["traceEvents"]
             if ev["ph"] == "C" and ev["name"] == "queue:e"]
    assert [ev["args"] for ev in depth] == [{"depth": 2}]
    spans = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    assert {"queued", "prefill_wait", "prefill"} <= spans
