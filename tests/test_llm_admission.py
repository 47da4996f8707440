"""Admission by the admitted rows' peak future demand, blocks granted as
a row grows (llm/paged_cache.py `peak_demand` / `reserve` / `grow`,
llm/engine.py `_peak_with` / `_grow`; ISSUE 38). On the CPU at tiny
sizes: the same tokens as `transformer.generate` in a pool the requests'
whole lives overflow, a growth grant that never fails, FIFO kept, every
admission whole-life reservation would make made too, and the schedule
of the benchmark's reason mix, its arithmetic at the true lengths and
the engine at an eighth of them."""

import json
import os
import random
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_hybrid                                              # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm.engine import ROW_STATES, LLMEngine     # noqa: E402
from nnstreamer_tpu.llm.paged_cache import (                    # noqa: E402
    PagedKVCache, peak_demand)
from nnstreamer_tpu.models.transformer import generate, init_params  # noqa: E402
from perfbench.references import hybrid_lm as hybrid_ref        # noqa: E402
from perfbench.runners.hybrid_llm import lm_spec as hybrid_spec  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "mixes",
                       "reason_backlog.json")) as f:
    REASON_ITEMS = [tuple(it) for it in json.load(f)["items"]]


@pytest.fixture(scope="module")
def params():
    return init_params(vocab=61, d_model=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, seed=0)


def _engine(params, **kw):
    given = dict(n_heads=4, block_size=4, num_blocks=32, max_batch=4,
                 max_len=64)
    return LLMEngine(params, **dict(given, **kw))


def _prompt(n, seed=0, vocab=61):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _ref(params, prompt, n, max_len=64):
    return [int(t) for t in np.asarray(generate(
        params, np.asarray(prompt)[None, :], n, n_heads=4,
        max_len=max_len))[0, len(prompt):]]


def _whole(eng, req):
    return eng.cache.blocks_for(len(req.prompt) + req.max_new_tokens)


def _holders(eng):
    """Every request that holds blocks, whatever list it stands in."""
    return [r for r in eng.active + eng.prefilling if r.block_table]


def _watch(eng):
    """Wrap `_admit_queue`: the order of admission, and at every refusal
    for blocks what whole-life reservation would have said of the same
    rows and the same head."""
    seen = {"order": [], "old_would_admit": 0}
    inner = eng._admit_queue

    def admit(pending):
        before = list(eng.queue)
        inner(pending)
        seen["order"].extend(r.req_id for r in before[:len(before)
                                                     - len(eng.queue)])
        if eng._free_cause == "blocked":
            held = sum(_whole(eng, r) for r in
                       _holders(eng) + [r for r, _ in pending])
            if held + _whole(eng, eng.queue[0]) <= eng.cache.allocator.total:
                seen["old_would_admit"] += 1

    eng._admit_queue = admit
    return seen


# -- the arithmetic -----------------------------------------------------------

def test_peak_demand_by_hand():
    # one row at position 5 with 7 launches left, blocks of 4: at its
    # last launch it writes position 11 and holds 3 blocks
    assert peak_demand([(5, 7)], 4) == 3
    # a row that ends first leaves before the other has grown: the peak
    # is where both are live (j = 2: 2 + 2) or the long one alone (5)
    assert peak_demand([(6, 2), (3, 17)], 4) == 5
    assert peak_demand([(6, 2), (3, 9)], 4) == 4
    # what is not decoding yet counts at every launch
    assert peak_demand([(6, 2), (3, 9)], 4, held=6) == 10
    assert peak_demand([], 4, held=6) == 6 and peak_demand([], 4) == 0


@pytest.mark.parametrize("seed", range(6))
def test_peak_demand_is_the_largest_launch_and_under_the_whole_lives(seed):
    rng = random.Random(seed)
    bs = rng.choice([1, 2, 4, 16])
    rows = [(rng.randrange(1, 200), rng.randrange(1, 300))
            for _ in range(rng.randrange(1, 20))]

    def held_at(j):
        return sum(-(-(p + j) // bs) for p, k in rows if k >= j)

    every = max(held_at(j) for j in range(1, max(k for _, k in rows) + 1))
    assert peak_demand(rows, bs) == every
    assert held_at(1) <= every <= sum(-(-(p + k) // bs) for p, k in rows)


def _schedule(rule, total, rows, items, steps, block_size=16, seed=0):
    """The schedule alone, in integers: lock-step decode, FIFO, `items`
    dealt in seeded permutations; `rule` "whole" reserves a life, "peak"
    is `peak_demand`. Returns (decode row-steps, refusals, most blocks
    live)."""
    rng = random.Random(seed)
    bf = lambda n: -(-n // block_size)                      # noqa: E731
    queue = []
    live = []                               # [pos, launches left, whole]
    decode = refused = high = 0
    for _ in range(steps):
        while len(live) < rows:
            if not queue:
                queue = list(items)
                rng.shuffle(queue)
            plen, out = queue[0]
            if rule == "whole":
                fits = sum(r[2] for r in live) + bf(plen + out) <= total
            else:
                fits = peak_demand([(r[0], r[1]) for r in live]
                                   + [(plen, out)], block_size) <= total
            if not fits:
                refused += 1
                break
            queue.pop(0)
            # the first token is the prefill's: out - 1 launches
            live.append([plen, out - 1, bf(plen + out)])
        high = max(high, sum(bf(r[0] + 1) for r in live))
        decode += len(live)
        for r in live:
            r[0] += 1
            r[1] -= 1
        live = [r for r in live if r[1] >= 1]
    return decode, refused, high


@pytest.mark.parametrize("seed", range(3))
def test_the_reason_mix_at_its_true_lengths_fills_sixteen_rows(seed):
    """The cell's pool (743 blocks of 16), rows and mix: whole-life
    reservation holds 13.3-13.6 rows and refuses in every step; the peak
    rule holds all 16 with no refusal and never more live than the pool,
    and still does in a pool of 620."""
    steps = 3000
    decode, refused, _ = _schedule("whole", 743, 16, REASON_ITEMS, steps,
                                   seed=seed)
    assert 13.2 < decode / steps < 13.7 and refused == steps
    decode, refused, high = _schedule("peak", 743, 16, REASON_ITEMS, steps,
                                      seed=seed)
    assert decode == 16 * steps and refused == 0 and 560 < high <= 743
    decode, refused, high = _schedule("peak", 620, 16, REASON_ITEMS, steps,
                                      seed=seed)
    assert decode == 16 * steps and refused == 0 and high <= 620
    decode, refused, high = _schedule("peak", 500, 16, REASON_ITEMS, steps,
                                      seed=seed)
    assert decode < 15.5 * steps and refused > 0 and high <= 500


# -- the cache ----------------------------------------------------------------

def _cache(**kw):
    given = dict(num_blocks=8, block_size=4, n_layers=1, n_kv=1, head_dim=4)
    return PagedKVCache(**dict(given, **kw))


def test_reserve_refuses_by_the_peak_and_takes_nothing():
    c = _cache(state_shape=(1, 1, 2, 2), state_slots=2)
    assert c.reserve(2, owner="a", peak=8) == "blocks"      # 7 usable
    assert c.allocator.used == c.state_alloc.used == 0
    assert c.allocator.failed_allocs == 1
    blocks, slot = c.reserve(2, owner="a", peak=7)
    assert len(blocks) == 2 and slot == 1
    # the peak is no less than what is held with the grant
    assert c.reserve(6, owner="b") == "blocks"
    assert c.state_alloc.used == 1
    c.reserve(1, owner="b", peak=7)
    assert c.reserve(1, owner="c", peak=7) == "state"       # no slot left
    assert c.allocator.used == 3
    st = c.stats()
    assert st["admit_peak_blocks"] == 7 and st["blocks_grown"] == 0


def test_grow_appends_one_block_and_a_full_pool_raises():
    c = _cache(num_blocks=4)
    table, _ = c.reserve(2, owner="a", peak=3)
    first = list(table)
    c.grow(table, owner="a")
    assert table[:2] == first and len(table) == 3 and len(set(table)) == 3
    assert c.stats()["blocks_grown"] == 1
    assert c.stats()["blocks_live_high_water"] == 3
    with pytest.raises(BackendError, match="peak demand"):
        c.grow(table, owner="a")
    assert len(table) == 3
    c.release(table, None)
    assert c.allocator.used == 0


# -- (a) parity in a pool the whole lives overflow -----------------------------

def test_a_backlog_whose_lives_overflow_the_pool_decodes_side_by_side(params):
    """Eight requests of 3 + 29: a life is 8 blocks of 4, the pool has
    15, so whole-life reservation would hold one row (two lives are 16).
    Staggered by the rule itself, the rows' peak fits: more than one
    decodes at a time, each the tokens of `transformer.generate`."""
    eng = _engine(params, num_blocks=16, max_batch=4, max_len=32)
    seen = _watch(eng)
    reqs = [eng.submit(_prompt(3, seed=i), max_new_tokens=29)
            for i in range(8)]
    most = 0
    while eng.has_work:
        eng.step()
        most = max(most, len(_holders(eng)))
        assert eng.cache.allocator.used <= eng.cache.allocator.total
    total = eng.cache.allocator.total
    assert total // _whole(eng, reqs[0]) == 1 and most >= 2
    for r in reqs:
        assert r.tokens == _ref(params, r.prompt, 29, max_len=32)
    assert seen["order"] == [r.req_id for r in reqs]
    assert seen["old_would_admit"] == 0 and eng.admission_blocked > 0
    st = eng.stats()
    assert st["rows"]["decode"] / st["executor"]["decode_steps"] > 1.5
    cache = st["cache"]
    assert cache["blocks_used"] == 0 and cache["blocks_grown"] > 0
    assert cache["blocks_live_high_water"] <= cache["admit_peak_blocks"] \
        <= total


# -- (b) the property, seeded --------------------------------------------------

PROPERTY_CASES = {
    # num_blocks, max_batch, block_size, prompts up to, outputs up to, eos
    "tight_pool": (10, 4, 4, 6, 20, False),
    "tight_pool_eos": (10, 4, 4, 6, 20, True),
    "one_slot_blocks": (24, 4, 1, 5, 12, True),
    "wide_batch": (20, 8, 2, 4, 14, False),
    "wide_batch_eos": (20, 8, 2, 4, 14, True),
    "two_rows": (9, 2, 4, 9, 18, True),
    "sampled_rows": (12, 4, 4, 6, 16, False),
    "roomy": (64, 4, 4, 8, 16, True),
}


@pytest.mark.parametrize("case", sorted(PROPERTY_CASES))
def test_no_grant_fails_and_no_request_waits_longer(params, case):
    """Seeded lengths, budgets and stops: the pool is never overdrawn
    (a growth grant that failed would raise), admission is in the
    submitted order, a refusal for blocks is one whole-life reservation
    would have made of the same rows, and everything goes back."""
    num_blocks, max_batch, bs, pmax, omax, eos = PROPERTY_CASES[case]
    rng = np.random.default_rng(sorted(PROPERTY_CASES).index(case))
    eng = _engine(params, num_blocks=num_blocks, max_batch=max_batch,
                  block_size=bs, max_len=32)
    seen = _watch(eng)
    reqs = []
    for i in range(14):
        kw = {}
        if eos and i % 2:
            # a token the model does emit here: the row stops early
            kw["eos_id"] = _ref(params, _prompt(3, seed=i), 6,
                                max_len=32)[int(rng.integers(1, 6))]
        if case == "sampled_rows" and i % 3 == 0:
            kw.update(temperature=0.8, seed=i)
        plen = 3 if kw.get("eos_id") is not None \
            else int(rng.integers(1, pmax + 1))
        reqs.append(eng.submit(_prompt(plen, seed=i),
                               max_new_tokens=int(rng.integers(1, omax + 1)),
                               **kw))
    alloc = eng.cache.allocator
    while eng.has_work:
        eng.step()
        assert alloc.used <= alloc.total
        assert alloc.used == sum(len(r.block_table) for r in _holders(eng))
        for r in _holders(eng):
            assert len(r.block_table) <= _whole(eng, r)
    assert seen["order"] == [r.req_id for r in reqs]
    assert seen["old_would_admit"] == 0
    assert alloc.used == 0 and alloc.high_water <= alloc.total
    for r in reqs:
        assert r.finish_reason == "eos" or len(r.tokens) == r.max_new_tokens
        if r.temperature == 0.0:
            want = _ref(params, r.prompt, r.max_new_tokens, max_len=32)
            assert r.tokens == want[:len(r.tokens)]
    rows = eng.stats()["rows"]
    assert sum(rows[k] for k in ROW_STATES) == rows["total"]


# -- (c) the reason mix's schedule, an eighth the size ---------------------------

def _reason_engine(params, num_blocks):
    """The cell's pool and mix scaled down by eight: lengths / 8 in
    blocks of 2 slots, so the block counts are the cell's own."""
    eng = _engine(params, block_size=2, num_blocks=num_blocks,
                  max_batch=16, max_len=144)
    rng = random.Random(5)
    for i in range(11):
        items = list(REASON_ITEMS)
        rng.shuffle(items)
        for j, (plen, out) in enumerate(items):
            eng.submit(_prompt(plen // 8, seed=8 * i + j),
                       max_new_tokens=out // 8)
    return eng


def test_the_reason_mix_an_eighth_the_size_leaves_no_row_blocked(params):
    eng = _reason_engine(params, num_blocks=744)
    for _ in range(420):
        eng.step()
    assert eng.queue                        # the backlog is not through
    st = eng.stats()
    rows, cache = st["rows"], st["cache"]
    assert sum(rows[k] for k in ROW_STATES) == rows["total"]
    assert rows["blocked"] == 0 == st["admission_blocked"]
    assert rows["decode"] / rows["total"] >= 0.98
    assert cache["blocks_grown"] > 0
    assert 400 < cache["blocks_live_high_water"] \
        <= cache["admit_peak_blocks"] <= cache["blocks_total"] == 743


def test_two_thirds_of_that_pool_blocks_and_the_account_says_so(params):
    eng = _reason_engine(params, num_blocks=496)
    for _ in range(420):
        eng.step()
    st = eng.stats()
    rows = st["rows"]
    assert sum(rows[k] for k in ROW_STATES) == rows["total"]
    assert rows["total"] == 16 * st["executor"]["decode_steps"]
    assert rows["blocked"] > 0 and st["admission_blocked"] > 0
    assert rows["unfed"] == rows["other"] == rows["blocked_state"] == 0
    assert rows["decode"] / rows["total"] < 0.98
    assert st["cache"]["blocks_live_high_water"] <= 495


# -- (d) the other paths through admission --------------------------------------

def test_a_prompt_still_in_chunks_counts_at_its_whole_life(params):
    """A prompt of 20 in chunks of 8 with 12 to decode holds 6 blocks of
    4 and will hold 8; the pool has 9. A request of 4 + 4 (2 blocks at
    its longest) would fit beside what the prompt holds, and beside its
    growth once it decodes (7 + 2 at the short one's end, 8 alone), but
    not beside its whole life: it waits until the long one's first
    launch is known, not longer."""
    eng = _engine(params, num_blocks=10, max_batch=2, prefill_chunk=8,
                  max_len=32)
    a = eng.submit(_prompt(20), max_new_tokens=12)
    b = eng.submit(_prompt(4, seed=1), max_new_tokens=4)
    eng.step()
    assert a.state == "prefilling" and len(a.block_table) == 6
    assert b.state == "queued" and eng.admission_blocked == 1
    assert eng.cache.allocator.free == 3
    while a.state == "prefilling":
        assert b.state == "queued"
        eng.step()
    eng.step()
    assert b.state == "active"
    eng.drain()
    assert a.tokens == _ref(params, a.prompt, 12, max_len=32)
    assert b.tokens == _ref(params, b.prompt, 4, max_len=32)
    assert eng.cache.stats()["admit_peak_blocks"] == 9
    assert eng.cache.allocator.used == 0


@pytest.mark.parametrize("stop", range(1, 7))
def test_a_row_that_stops_after_its_launch_gives_back_what_it_grew(params,
                                                                   stop):
    """Run-ahead with blocks of 2: a row grows a block every second
    launch, the one made while its stop is unread among them. Whatever
    the launch grew goes back with the rest, once."""
    eng = _engine(params, block_size=2, num_blocks=14, max_batch=2,
                  max_len=32)
    prompt = _prompt(3, seed=29)         # its first seven tokens differ
    probe = _ref(params, prompt, 10, max_len=32)
    assert probe.index(probe[stop]) == stop
    a = eng.submit(prompt, max_new_tokens=10, eos_id=probe[stop])
    b = eng.submit(_prompt(2, seed=3), max_new_tokens=12)
    c = eng.submit(_prompt(4, seed=4), max_new_tokens=9)
    eng.drain()
    assert a.finish_reason == "eos" and a.tokens == probe[:stop + 1]
    assert eng.lookahead_discarded == 1
    assert b.tokens == _ref(params, b.prompt, 12, max_len=32)
    assert c.tokens == _ref(params, c.prompt, 9, max_len=32)
    assert eng.cache.allocator.used == 0 and not eng.cache.allocator._owner


def test_a_static_batch_forms_by_the_peak_and_runs_out(params):
    """`static_batching`: the batch that forms from empty is the one
    whose peak fits (three lives of 3 blocks would not fit 7; three rows
    of which one is short do), and nothing joins until it has run out."""
    eng = _engine(params, num_blocks=8, max_batch=4, max_len=16,
                  static_batching=True)
    work = [(2, 10), (2, 3), (2, 10), (2, 10)]
    reqs = [eng.submit(_prompt(n, seed=i), max_new_tokens=out)
            for i, (n, out) in enumerate(work)]
    eng.step()
    assert [r.state for r in reqs] == ["active"] * 3 + ["queued"]
    while reqs[0].state != "done":
        assert reqs[3].state == "queued"
        eng.step()
        assert eng.cache.allocator.used <= 7
    eng.drain()
    for r, (_, out) in zip(reqs, work):
        assert r.tokens == _ref(params, r.prompt, out, max_len=16)
    st = eng.stats()
    assert st["rows"]["other"] > 0 and st["cache"]["blocks_used"] == 0


@pytest.fixture(scope="module")
def hybrid():
    cfg = tiny_hybrid.CONFIG
    params = hybrid_ref.make_params(cfg, 2**31 + 5, dtype=jnp.float32)
    return params, ModelBundle(fn=None, lm=hybrid_spec(cfg), params=params)


def test_a_hybrid_rows_compressed_keys_follow_its_growing_table(hybrid):
    """Two rows of the hybrid family grow in turns, so their tables
    interleave in the pool, past the 24 tokens at which a query has more
    selection blocks than it may attend: entry m of a row's slot stays
    block m of its table, and the tokens are the reference's. A refusal
    for blocks takes no slot."""
    params, bundle = hybrid
    cfg = tiny_hybrid.CONFIG
    eng = LLMEngine(bundle, dtype=jnp.float32, max_batch=2, prefill_chunk=8,
                    block_size=4, num_blocks=24, max_len=64)
    work = [(_prompt(6, seed=6, vocab=256), 22),
            (_prompt(13, seed=7, vocab=256), 14),
            (_prompt(5, seed=8, vocab=256), 4)]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
    tables = {}
    while eng.has_work:
        eng.step()
        st = eng.cache.stats()
        assert st["state_slots_used"] == len(_holders(eng))
        for r in _holders(eng):
            seen = tables.setdefault(r.req_id, [])
            assert r.block_table[:len(seen)] == seen    # appended to only
            tables[r.req_id] = list(r.block_table)
    a, b = (tables[r.req_id] for r in reqs[:2])
    assert len(a) == len(b) == 7
    # interleaved: neither row's blocks are one run of the pool
    assert sorted(a) != list(range(min(a), min(a) + len(a)))
    for r, (p, n) in zip(reqs, work):
        ids = list(p)
        for _ in range(n):
            logits = hybrid_ref.forward_logits(params, cfg, np.asarray(ids),
                                               q_block=8)
            ids.append(int(np.argmax(np.asarray(logits[-1]))))
        assert list(r.tokens) == ids[len(p):]
    st = eng.stats()
    assert st["cache"]["blocks_used"] == st["cache"]["state_slots_used"] == 0
    assert st["cache"]["blocks_grown"] >= 8


def test_a_hybrid_admission_short_of_blocks_takes_no_slot(hybrid):
    _, bundle = hybrid
    eng = LLMEngine(bundle, dtype=jnp.float32, max_batch=2, prefill_chunk=8,
                    block_size=4, num_blocks=8, max_len=24)
    a = eng.submit(_prompt(5, vocab=256), max_new_tokens=14)     # 5 blocks
    b = eng.submit(_prompt(5, seed=1, vocab=256), max_new_tokens=14)
    eng.step()
    assert a.state == "active" and b.state == "queued"
    assert b.state_slot is None and eng.cache.state_alloc.used == 1
    assert eng.admission_blocked == 1 == eng.cache.allocator.failed_allocs
    assert eng.admission_blocked_state == 0
    eng.drain()
    assert len(a.tokens) == len(b.tokens) == 14
    assert eng.cache.state_alloc.used == eng.cache.allocator.used == 0
