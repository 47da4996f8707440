"""The hybrid family (llm/hybrid_lm.py: linear attention with a carried
state, block-sparse attention over compressed keys) against its plain
reference in float32, and the two kinds of state it keeps (paged blocks
and a state slot a sequence) through the executor and the engine."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_hybrid as tiny                                      # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import hybrid_lm, parts                 # noqa: E402
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.paged_cache import (                    # noqa: E402
    BlockAllocator, PagedKVCache)
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from nnstreamer_tpu.serving.store import get_store, reset_store  # noqa: E402
from perfbench.references import hybrid_lm as ref               # noqa: E402
from perfbench.runners.hybrid_llm import (                      # noqa: E402
    EXECUTOR_COUNTERS, lm_spec)

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
SEED = 2**31 + 5
POOL = dict(block_size=4, num_blocks=80, max_len=64)
TOL = 1e-4          # float32 on the CPU: sums in another order only


@pytest.fixture(scope="module")
def params():
    return ref.make_params(CFG, SEED, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bundle(params):
    return ModelBundle(fn=None, params=params, lm=SPEC)


def _executor(bundle, **kw):
    return PagedLLMExecutor(bundle, dtype=jnp.float32, state_slots=4,
                            **dict(POOL, **kw))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# -- the two mixers against their definitions ---------------------------------

def _qkv(n, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(n, 4, 16)), jnp.float32)
                 for _ in range(3))


def test_reference_quadratic_form_is_the_recurrence():
    q, k, v = _qkv(40)
    slopes = ref.decay_slopes(4)
    assert np.allclose(ref.linear_attention(q, k, v, slopes, 8),
                       ref.linear_recurrence(q, k, v, slopes), atol=2e-5)


@pytest.mark.parametrize("real", [64, 37, 5])
def test_chunked_scan_is_the_token_by_token_recurrence(real, monkeypatch):
    """Runs of 16 tokens, a carried state, padding past `real`: outputs
    of the real tokens and the state handed on equal the recurrence's
    over the real tokens alone."""
    monkeypatch.setattr(hybrid_lm, "_SCAN", 16)
    q, k, v = _qkv(64)
    slopes = ref.decay_slopes(4)
    before = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 16)),
                         jnp.float32)
    o, after = hybrid_lm.linear_scan(q, k, v, jnp.arange(64) < real, before,
                                     jnp.float32)
    lam = jnp.exp(-slopes)[:, None, None]
    state, want = before, []
    for t in range(real):
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        want.append(jnp.einsum("hd,hde->he", q[t] / 4.0, state))
    assert np.allclose(o[:real], jnp.stack(want), atol=2e-5)
    assert np.allclose(after, state, atol=2e-5)


def test_the_cut_drops_blocks_at_the_tiny_size(params):
    """Past three selection blocks of eight tokens a query attends fewer
    positions than it has behind it, and more blocks change the logits."""
    taps = {}
    ids = _prompt(45)
    logits = ref.forward_logits(params, CFG, ids, q_block=8, taps=taps)
    att = taps["attended"]                      # (sparse layers, S, G)
    assert att.shape == (2, 45, 2)
    assert (att[:, :24] == np.arange(1, 25)[None, :, None]).all()
    assert (att[:, 24:] < np.arange(25, 46)[None, :, None]).all()
    wider = dict(CFG, assumed_sizes=dict(CFG["assumed_sizes"],
                                         sparse_topk=8))
    dense = ref.forward_logits(params, wider, ids, q_block=8)
    assert np.abs(dense - logits)[:24].max() == 0.0
    assert np.abs(dense - logits)[24:].max() > 1e-3


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("plen,chunk", [(37, 0), (37, 8), (30, 16),
                                        (41, 12), (29, 6)])
def test_prefill_then_decode_equals_one_forward_pass(bundle, params, plen,
                                                     chunk):
    """Whole-prompt or by chunks (the last one short; 12 does not divide
    a selection block, 6 not a block of the pool), then decode through the cache and the state:
    logits of every step against the reference's one forward pass."""
    ids = _prompt(plen + 8, seed=plen)
    want = np.asarray(ref.forward_logits(params, CFG, ids, q_block=8))
    ex = _executor(bundle)
    blocks, slot = ex.cache.reserve(ex.cache.blocks_for(len(ids)))
    if chunk:
        for at in range(0, plen, chunk):
            got = ex.prefill_chunk(ids[at:min(at + chunk, plen)], at,
                                   blocks, bucket=16, state_slot=slot)
    else:
        got = ex.prefill(ids[:plen], blocks, state_slot=slot)
    assert np.abs(got - want[plen - 1]).max() < TOL
    for t in range(plen, plen + 8):
        got = ex.decode([int(ids[t])], [blocks], [t], state_slots=[slot])
        assert np.abs(got[0] - want[t]).max() < TOL


def test_a_window_of_two_blocks_and_three_chosen_ones(params):
    """Another geometry at the same weights: six blocks a query, a window
    of two, so a query past six blocks chooses three of up to five by
    score; chunks whose queries' windows start in different blocks."""
    cfg = dict(CFG, assumed_sizes=dict(
        CFG["assumed_sizes"], sparse_topk=6, sparse_window_size=16))
    ids = _prompt(61, seed=11)
    taps = {}
    want = np.asarray(ref.forward_logits(params, cfg, ids, q_block=8,
                                         taps=taps))
    assert (taps["attended"][:, 48:] < np.arange(49, 62)[None, :, None]).all()
    ex = _executor(ModelBundle(fn=None, params=params, lm=lm_spec(cfg)))
    blocks, slot = ex.cache.reserve(16)
    for at in (0, 16, 32):
        got = ex.prefill_chunk(ids[at:min(at + 16, 45)], at, blocks,
                               bucket=16, state_slot=slot)
    assert np.abs(got - want[44]).max() < TOL
    for t in range(45, 61):
        got = ex.decode([int(ids[t])], [blocks], [t], state_slots=[slot])
        assert np.abs(got[0] - want[t]).max() < TOL
    # the whole prompt as one chunk of 64: a tile four blocks wide
    ex = _executor(ModelBundle(fn=None, params=params, lm=lm_spec(cfg)))
    blocks, slot = ex.cache.reserve(16)
    got = ex.prefill(ids, blocks, state_slot=slot)
    assert np.abs(got - want[60]).max() < TOL


def test_rows_keep_their_state_apart(bundle, params):
    """Three rows at different depths in one decode bucket of four (one
    padding row): each row's logits are its own forward pass's, and the
    slots of the other sequences and the pool's fourth slot are
    untouched by a step that does not name them."""
    ex = _executor(bundle)
    seqs = [_prompt(n + 4, seed=n) for n in (9, 26, 33)]
    want = [np.asarray(ref.forward_logits(params, CFG, s, q_block=8))
            for s in seqs]
    held = []
    for s in seqs:
        blocks, slot = ex.cache.reserve(ex.cache.blocks_for(len(s)))
        ex.prefill(s[:-4], blocks, state_slot=slot)
        held.append((blocks, slot))
    free = [s for s in range(1, 5) if s not in [slot for _, slot in held]]
    assert len(free) == 1
    for step in range(4):
        before = np.asarray(ex.cache.state)
        pos = [len(s) - 4 + step for s in seqs]
        got = ex.decode([int(s[p]) for s, p in zip(seqs, pos)],
                        [b for b, _ in held], pos,
                        state_slots=[slot for _, slot in held])
        for i, p in enumerate(pos):
            assert np.abs(got[i] - want[i][p]).max() < TOL
        after = np.asarray(ex.cache.state)
        assert (after[:, free] == before[:, free]).all()
    # a step of two of the rows leaves the third's state as it was
    before = np.asarray(ex.cache.state)
    ex.decode([1, 2], [held[0][0], held[2][0]],
              [len(seqs[0]), len(seqs[2])],
              state_slots=[held[0][1], held[2][1]])
    after = np.asarray(ex.cache.state)
    assert (after[:, held[1][1]] == before[:, held[1][1]]).all()
    assert (after[:, held[0][1]] != before[:, held[0][1]]).any()


def test_a_new_owner_of_a_slot_starts_from_zero(bundle, params):
    """A slot that held another sequence's state: the next sequence's
    first chunk does not read it."""
    ex = _executor(bundle)
    first, second = _prompt(20, seed=3), _prompt(17, seed=4)
    blocks, slot = ex.cache.reserve(8)
    ex.prefill(first, blocks, state_slot=slot)
    ex.cache.release(blocks, slot)
    blocks, again = ex.cache.reserve(8)
    assert again == slot
    got = ex.prefill(second, blocks, state_slot=again)
    want = np.asarray(ref.forward_logits(params, CFG, second, q_block=8))
    assert np.abs(got - want[-1]).max() < TOL


# -- two kinds of state in one cache manager ----------------------------------

def test_the_cache_builds_the_family_its_pools(bundle):
    ex = _executor(bundle)
    c = ex.cache
    # the two sparse layers' two KV heads, a pool layer each
    assert c.k.shape == c.v.shape == (4, 80, 4, 1, 16)
    # by slot: a compressed key a block of the longest table, a state
    assert c.slot_rows.shape == (4, 5, 16, 16) and c.idx is None
    assert c.state.shape == (2, 5, 4, 16, 16)
    assert c.state.dtype == jnp.float32
    assert [p.shape for p in c.pools()] == [c.k.shape, c.v.shape,
                                            c.slot_rows.shape, c.state.shape]
    st = c.stats()
    assert st["pools"] == 4 and st["state_slots"] == 4
    assert st["state_slots_used"] == 0
    assert st["state_slot_bytes"] == 2 * 4 * 16 * 16 * 4 + 4 * 16 * 16 * 4
    assert st["state_bytes"] == 5 * st["state_slot_bytes"]
    # K and V of a block's four tokens, 2 layers' 2 heads
    assert st["block_bytes"] == 2 * 2 * 4 * 2 * 16 * 4
    assert ex.resident_bytes() >= c.resident_bytes() > st["state_bytes"]


def test_reserve_grants_both_or_neither():
    c = PagedKVCache(num_blocks=8, block_size=4, n_layers=1, n_kv=1,
                     head_dim=8, state_shape=(1, 1, 8, 8), state_slots=2)
    a = c.reserve(3, owner="a")
    b = c.reserve(3, owner="b")
    assert a == ([1, 2, 3], 1) and b == ([4, 5, 6], 2)
    assert c.reserve(1, owner="c") == "state"       # a block, no slot
    c.release(*b)
    assert c.reserve(5, owner="c") == "blocks"      # a slot, four blocks
    assert c.state_alloc.used == 1 and c.allocator.used == 3
    c.release(*a)
    assert c.state_alloc.used == 0 and c.allocator.used == 0
    plain = PagedKVCache(num_blocks=8, block_size=4, n_layers=1, n_kv=1,
                         head_dim=8)
    assert plain.reserve(2) == ([1, 2], None)
    assert "state_slots" not in plain.stats()


def _engine(model, **kw):
    given = dict(dtype=jnp.float32, max_batch=2, prefill_chunk=8, **POOL)
    return LLMEngine(model, **dict(given, **kw))


def _greedy(params, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        logits = ref.forward_logits(params, CFG, np.asarray(ids), q_block=8)
        ids.append(int(np.argmax(np.asarray(logits[-1]))))
    return ids[len(prompt):]


def test_the_engine_serves_the_family_and_gives_everything_back(bundle,
                                                                params):
    """Chunked and whole prompts, launch-ahead decode with its early
    release: the reference's greedy tokens, and both allocators empty
    when every request is done."""
    tracer = Tracer(max_events=8192)
    eng = _engine(bundle, tracer=tracer)
    work = [(_prompt(21, seed=5), 5), (_prompt(6, seed=6), 7),
            (_prompt(33, seed=7), 4)]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
    seen_used = 0
    while eng.has_work:
        eng.step()
        st = eng.cache.stats()
        assert st["state_slots_used"] == len(eng.active) + len(
            eng.prefilling)
        seen_used = max(seen_used, st["state_slots_used"])
    assert seen_used == 2
    for r, (p, n) in zip(reqs, work):
        assert list(r.tokens) == _greedy(params, p, n)
        assert r.state_slot is None and r.block_table == []
    st = eng.stats()
    assert st["lookahead_steps"] > 0
    assert st["cache"]["blocks_used"] == st["cache"]["state_slots_used"] == 0
    assert st["admission_blocked"] == st["admission_blocked_state"] == 0
    ex = st["executor"]
    assert ex["family"] == "hybrid"
    assert all(isinstance(ex[k], int) and ex[k] > 0
               for k in EXECUTOR_COUNTERS)
    spans = [(label, args) for ph, cat, _, label, _, _, args
             in tracer.events() if ph == "X" and args]
    admits = [a for label, a in spans if label.startswith("admit")]
    assert admits and all("state_free" in a for a in admits)
    steps = [a for label, a in spans if label == "invoke"
             and a.get("what") == "llm_decode"]
    assert steps and all(
        {"state_rows", "ckeys_scored", "blocks_selected", "kv_selected",
         "kv_slots"} <= set(a) for a in steps)
    chunks = [a for label, a in spans if label == "invoke"
              and a.get("what") == "llm_prefill_chunk"]
    assert chunks and all({"pos0", "clen", "state_rows", "ckeys_scored",
                           "kv_selected"} <= set(a) for a in chunks)


@pytest.mark.parametrize("every", [1, 3])
def test_chunks_ride_every_nth_step_beside_live_rows(bundle, params, every):
    """`chunk_every`: while a row decodes, a prefilling prompt advances
    one chunk every N-th step and the steps between are the decode
    batch alone; with no row decoding a chunk rides every step; the
    tokens are the reference's either way."""
    eng = _engine(bundle, chunk_every=every)
    short, long_ = _prompt(6, seed=11), _prompt(41, seed=12)
    a = eng.submit(short, max_new_tokens=24)
    b = eng.submit(long_, max_new_tokens=3)
    rode = []                   # (a row was decoding, a chunk rode)
    while eng.has_work:
        live, before = bool(eng.active), b.pos
        eng.step()
        if before < 41:
            rode.append((live, b.pos > before))
    beside = [r for live, r in rode if live]
    assert all(beside[i] == (i % every == every - 1)
               for i in range(len(beside)))
    assert all(r for live, r in rode if not live)
    assert list(a.tokens) == _greedy(params, short, 24)
    assert list(b.tokens) == _greedy(params, long_, 3)
    assert eng.stats()["chunk_every"] == every


def test_the_decode_bucket_of_the_rows_admitted_is_built_at_admission(
        bundle, params):
    """Four prompts of three chunks each and three tokens out, a chunk
    every fourth step: admitted together, never more than two live
    together. The bucket of four rows is built at the step that admits
    them, before any has joined; the tokens are the reference's."""
    eng = _engine(bundle, max_batch=4, chunk_every=4)
    prompts = [_prompt(21, seed=20 + i) for i in range(4)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]

    def built():
        return {key[2] for key in eng.executor._jits if key[1] == "decode"}

    eng.step()
    assert len(eng.prefilling) == 4 and not eng.active
    assert built() == {4}
    live = 0
    while eng.has_work:
        eng.step()
        live = max(live, len(eng.active))
    assert live < 3 and built() == {1, 2, 4}
    for r, p in zip(reqs, prompts):
        assert list(r.tokens) == _greedy(params, p, 3)


def test_chunk_every_below_one_is_refused(bundle):
    with pytest.raises(BackendError, match="chunk_every"):
        _engine(bundle, chunk_every=0)


def test_counts_of_a_step_are_the_references(bundle, params):
    """What a decode step's span says it attended is what the reference
    attends at that position; a chunk's too, and its `kv_slots` are what
    its walk reads."""
    taps = {}
    ids = _prompt(41, seed=8)
    ref.forward_logits(params, CFG, ids, q_block=8, taps=taps)
    ps = _executor(bundle).programs
    for t in (3, 23, 24, 40):
        said = ps.note_decode(np.array([t, 0], np.int32), 1)
        assert said["kv_selected"] == taps["attended"][0, t, 0]
        assert said["state_rows"] == 1 and said["kv_tokens"] == t + 1
        # compressed keys of 8 tokens every 4, complete by t
        assert said["ckeys_scored"] == max(0, (t + 1 - 8) // 4 + 1)
        assert said["blocks_selected"] == min(3, t // 8 + 1)
    assert ps.counters["state_bytes_rw"] == 4 * 2 * ps.state_bytes
    read = ps.counters["kv_slots_read"]
    chunk = ps.note_chunk(16, 8, 8)
    assert chunk["kv_selected"] == taps["attended"][0, 16:24, 0].sum()
    assert chunk["pos0"] == 16 and chunk["state_rows"] == 1
    # the one live context tile, read once for all eight queries (the
    # table's 64 slots and the scratch block past them)
    assert chunk["ctx_tiles"] == 1
    assert chunk["kv_slots"] == parts.CTX_TILE
    assert ps.counters["kv_slots_read"] == read + chunk["kv_slots"]
    assert ps.note_chunk(16, 8, 8) == chunk            # reckoned once


def test_admission_short_of_a_slot_is_counted_apart(bundle):
    """Two rows, one state slot (an engine has one a row: the test
    narrows the allocator): the second request waits for the slot
    (`admission_blocked_state`), not for blocks; with slots to spare and
    a pool of one request's blocks it waits for blocks."""
    tracer = Tracer(max_events=4096)
    eng = _engine(bundle, tracer=tracer)
    eng.cache.state_alloc = BlockAllocator(2)       # the scratch and one
    reqs = [eng.submit(_prompt(5, seed=i), max_new_tokens=4)
            for i in range(2)]
    eng.drain()
    st = eng.stats()
    assert st["admission_blocked_state"] > 0 and st["admission_blocked"] == 0
    assert all(len(r.tokens) == 4 for r in reqs)
    assert st["cache"]["state_slots_used"] == st["cache"]["blocks_used"] == 0
    labels = {ev[3] for ev in tracer.events() if ev[0] == "X"}
    assert "admit_blocked_state" in labels and "admit_blocked" not in labels
    eng = _engine(bundle, num_blocks=4)
    reqs = [eng.submit(_prompt(5, seed=i), max_new_tokens=4)
            for i in range(2)]
    eng.drain()
    st = eng.stats()
    assert st["admission_blocked"] > 0 and st["admission_blocked_state"] == 0
    assert all(len(r.tokens) == 4 for r in reqs)


def test_hot_swap_keeps_live_state_and_serves_the_new_weights(params):
    """A swap to a version of the same description with a request in
    flight: the request goes on from its state and finishes, and the
    next request is the new version's from its first token."""
    reset_store()
    try:
        store = get_store()
        p2 = ref.make_params(CFG, SEED + 1, dtype=jnp.float32)
        store.register("hybrid_swap", ModelBundle(fn=None, params=params,
                                                  lm=SPEC))
        eng = _engine("store://hybrid_swap")
        prompt = _prompt(13, seed=9)
        live = eng.submit(prompt, max_new_tokens=9)
        for _ in range(4):
            eng.step()
        store.register("hybrid_swap", ModelBundle(fn=None, params=p2,
                                                  lm=SPEC))
        store.update("hybrid_swap")
        eng.drain()
        assert eng.executor.swap_count == 1 and len(live.tokens) == 9
        assert list(live.tokens)[:2] == _greedy(params, prompt, 2)
        fresh = eng.submit(prompt, max_new_tokens=3)
        eng.drain()
        assert list(fresh.tokens) == _greedy(p2, prompt, 3)
        st = eng.stats()["cache"]
        assert st["state_slots_used"] == st["blocks_used"] == 0
    finally:
        reset_store()


# -- what the family refuses --------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(shards=2), "shards=2"),
    (dict(paged_kernel="pallas"), "paged_kernel=pallas"),
    (dict(block_size=8), "block_size=8"),
])
def test_refusals_at_construction(bundle, kw, match):
    with pytest.raises(BackendError, match=match):
        PagedLLMExecutor(bundle, dtype=jnp.float32, **dict(POOL, **kw))


def test_refuses_a_quantized_store_version(params):
    blocks = [dict(b, wo_scale=jnp.ones((1,))) for b in params["blocks"]]
    with pytest.raises(BackendError, match="W8A8"):
        PagedLLMExecutor(ModelBundle(
            fn=None, params=dict(params, blocks=blocks), lm=SPEC),
            dtype=jnp.float32, **POOL)


def test_refuses_a_long_prompt_without_chunks(bundle, monkeypatch):
    from nnstreamer_tpu.llm import families

    monkeypatch.setattr(families.HybridSet, "WHOLE_PROMPT_MAX", 16)
    eng = _engine(bundle, prefill_chunk=0)
    with pytest.raises(BackendError, match="chunked prefill"):
        eng.submit(_prompt(17), max_new_tokens=2)
    eng.submit(_prompt(16), max_new_tokens=2)
