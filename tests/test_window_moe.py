"""The window family (llm/window_moe.py: layers that attend a window or
the whole context over a pool a kind, a dense layer in front, a shared
expert beside a share of the routed experts) against its plain reference
in float32, and the two tables a sequence keeps through the cache, the
executor and the engine."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_sparse_moe                                          # noqa: E402
import tiny_window_moe as tiny                                  # noqa: E402
from nnstreamer_tpu.backends import pallas_ops                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import experts, parts, window_moe        # noqa: E402
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.paged_cache import (                    # noqa: E402
    PagedKVCache, peak_demand, window_cap)
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import sparse_moe_lm                  # noqa: E402
from perfbench.references import window_moe_lm as ref           # noqa: E402
from perfbench.runners import sparse_moe_llm                    # noqa: E402
from perfbench.runners.window_moe_llm import lm_spec            # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
SEED = 2**31 + 7
WINDOW, BS, CHUNK = 8, 4, 4
POOL = dict(block_size=BS, num_blocks=48, max_len=64)
TOL = 1e-4          # float32 on the CPU: sums in another order only


@pytest.fixture(scope="module")
def params():
    return ref.make_params(CFG, SEED, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bundle(params):
    return ModelBundle(fn=None, params=params, lm=SPEC)


def _executor(bundle, **kw):
    return PagedLLMExecutor(bundle, dtype=jnp.float32, state_slots=4,
                            prefill_chunk=CHUNK, **dict(POOL, **kw))


def _engine(bundle, **kw):
    return LLMEngine(bundle, dtype=jnp.float32, max_batch=4,
                     prefill_chunk=CHUNK, **dict(POOL, **kw))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# -- chunks, then decode, through both pools, on logits -------------------------

def _serve(ex, ids, plen):
    """ids teacher-forced through the executor: the prompt's first `plen`
    in chunks, the rest a decode step each, the window table grown before
    a call and trimmed after it as the engine does. Returns the logits
    after positions plen - 1 .. len(ids) - 1, and the most window blocks
    the sequence held at a call."""
    cache = ex.cache
    blocks, _, wtab = cache.reserve(cache.blocks_for(len(ids)), window=0)
    first, held, out = 0, 0, []

    def grow(upto):
        while len(wtab) < cache.blocks_for(upto):
            cache.grow(wtab, window=True)
        return len(wtab) - first

    for at in range(0, plen, CHUNK):
        n = min(CHUNK, plen - at)
        held = max(held, grow(at + n))
        lg = ex.prefill_chunk(ids[at:at + n], at, blocks, bucket=CHUNK,
                              window_table=wtab)
        first = cache.trim(wtab, at + n, first)
    out.append(np.asarray(lg))
    for t in range(plen, len(ids)):
        held = max(held, grow(t + 1))
        out.append(ex.decode([int(ids[t])], [blocks], [t],
                             window_tables=[wtab])[0])
        first = cache.trim(wtab, t + 1, first)
    cache.release(blocks, None, wtab[first:])
    return np.stack(out), held


# a context that stays inside the window of 8, one that crosses it inside
# a chunk, one that crosses it while decoding, and a long one
@pytest.mark.parametrize("plen,total", [(5, 7), (14, 20), (6, 14), (33, 45)])
@pytest.mark.parametrize("tile", [8, 1024])
def test_chunks_then_decode_give_the_references_logits(bundle, params,
                                                       monkeypatch, plen,
                                                       total, tile):
    # the tile is a static argument of the chunk program: a small one
    # makes the walks' bounds (first tile, end) do the work
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    ids = _prompt(total, seed=plen)
    ex = _executor(bundle)
    got, held = _serve(ex, ids, plen)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[plen - 1:]
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert held <= window_cap(WINDOW, BS, CHUNK)
    assert ex.cache.allocator.used == ex.cache.window_alloc.used == 0


# -- the chunk's walk through the causal kernel ----------------------------------

@pytest.fixture
def forced(monkeypatch):
    """The predicate says yes whatever the backend and the shapes, and a
    program takes 2 queries of a chunk's bucket of 8, so that a walk has
    blocks of every kind; the kernel then runs in interpret mode."""
    monkeypatch.setattr(parts, "fused_attend", lambda c, tile, hd: True)
    monkeypatch.setattr(pallas_ops, "causal_block_q", lambda c, grp: 2)


def _qblocks_by_hand(pos0, bucket, tile, bq=2):
    """(clear, edge, skipped) over the tiny model's layers (S S F) for a
    chunk at `pos0`: every (block, tile) pair of each layer's walk, by a
    loop over its (query, slot) pairs."""
    said = [0, 0, 0]
    for window in (WINDOW, WINDOW, 0):
        first, end = parts.tile_span(pos0, bucket, 64, tile, window)
        for j in range(first, end):
            for q0 in range(pos0, pos0 + bucket, bq):
                pairs = [s <= q and (not window or s > q - window)
                         for q in range(q0, q0 + bq)
                         for s in range(j * tile, (j + 1) * tile)]
                said[0 if all(pairs) else 2 if not any(pairs) else 1] += 1
    return said


@pytest.mark.parametrize("tile", [4, 8])
def test_the_walk_through_the_causal_kernel_gives_the_references_logits(
        bundle, params, monkeypatch, forced, tile):
    """The fused walk (`pallas_ops.causal_block_update`, interpreted) on
    a context past the window, tiles smaller than the window and as wide:
    the reference's logits, and the chunk's span says what the kernel's
    programs did, which adds up to the walks' trip counts x blocks."""
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    ids = _prompt(45, seed=33)
    tracer = Tracer(max_events=8192)
    ex = _executor(bundle, tracer=tracer, name="llm")
    assert ex.programs.chunk_kw(0, CHUNK)["fused"] is True
    got, _ = _serve(ex, ids, 33)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[32:]
    assert np.abs(got - want).max() < TOL
    chunks = [a for ph, cat, _, label, _, _, a in tracer.events()
              if ph == "X" and cat == "backend" and label == "invoke"
              and a.get("what") == "llm_prefill_chunk"]
    assert len(chunks) == 8         # 9 chunks, the first one compiled
    kinds = ("chunk_qblocks_clear", "chunk_qblocks_edge",
             "chunk_qblocks_skipped")
    for a in chunks:
        assert a["attend"] == "fused"
        said = [a[k] for k in kinds]
        # a chunk of 4 rides a bucket of 8: 4 blocks of 2 queries
        assert a["bucket"] == 8
        assert said == _qblocks_by_hand(a["pos0"], 8, tile)
        assert sum(said) == (a["ctx_tiles_full"]
                             + 2 * a["ctx_tiles_window"]) * 4
    st = ex.stats()
    first = _qblocks_by_hand(0, 8, tile)
    for i, k in enumerate(kinds):
        assert st[k] == first[i] + sum(a[k] for a in chunks)
    # behind the window a window layer skips, under the diagonal it is clear
    assert all(st[k] > 0 for k in kinds)


def test_on_the_cpu_the_walk_is_plain_and_counts_no_program(bundle):
    tracer = Tracer(max_events=4096)
    ex = _executor(bundle, tracer=tracer, name="llm")
    _serve(ex, _prompt(20, seed=1), 14)
    chunks = [a for ph, cat, _, label, _, _, a in tracer.events()
              if ph == "X" and label == "invoke" and a
              and a.get("what") == "llm_prefill_chunk"]
    assert chunks and all(a["attend"] == "plain" for a in chunks)
    st = ex.stats()
    for k in ("chunk_qblocks_clear", "chunk_qblocks_edge",
              "chunk_qblocks_skipped"):
        assert st[k] == 0 and all(a[k] == 0 for a in chunks)


def test_padding_queries_past_the_tables_last_tile_attend_nothing():
    """A bucket whose padding rows lie past the table: the walk ends at
    the table's last tile, those queries see no slot of any tile, and
    the fused walk returns zeros for them as the plain one does."""
    rng = np.random.default_rng(2)
    c, tile, nkv, grp, hd = 16, 8, 2, 3, 16
    tab = jnp.asarray([3, 1, 4, 2], jnp.int32)         # 4 blocks of 4
    q = jnp.asarray(rng.normal(size=(c, nkv * grp, hd)), jnp.float32)
    pools = [jnp.asarray(rng.normal(size=(1, 6, 4, nkv, hd)), jnp.float32)
             for _ in range(2)]
    qpos = 8 + jnp.arange(c)                           # 8..23: 16.. past it
    span = parts.tile_span(8, c, 16, tile)
    assert span == (0, 2)
    out = [window_moe.attend_tiles(q, qpos, tab, span, 0, *pools, window=0,
                                   fused=fused, tile=tile, dtype=jnp.float32)
           for fused in (False, True)]
    assert np.abs(np.asarray(out[0]) - np.asarray(out[1])).max() < TOL
    assert float(jnp.abs(out[1][:8]).max()) > 0.1


def test_a_window_layer_alone_forgets_what_is_behind_its_window(params):
    """The reference itself: with every layer sliding, logits at a
    position do not move when a token more than the layers' reach behind
    it changes; with the full layer they do."""
    ids = _prompt(40, seed=3)
    other = ids.copy()
    other[2] = (other[2] + 1) % 256
    sliding = dict(CFG, layer_types=[ref.SLIDING] * 3)
    far = 2 + 3 * (WINDOW - 1) + 1          # three layers' reach past it
    a, b = (np.asarray(ref.forward_logits(params, sliding, x))
            for x in (ids, other))
    assert np.abs(a[far:] - b[far:]).max() == 0.0
    assert np.abs(a[:far] - b[:far]).max() > 0.0
    a, b = (np.asarray(ref.forward_logits(params, CFG, x))
            for x in (ids, other))
    assert np.abs(a[far:] - b[far:]).max() > 0.0


def test_work_list_with_a_lower_bound():
    """`parts.live_items` with `lo`: a row holds the chunks from lo // C on,
    and each item says where its chunk's live slots begin."""
    tables = jnp.asarray(np.arange(24).reshape(2, 12) + 1, jnp.int32)
    pos = jnp.asarray([21, 6], jnp.int32)
    lo = jnp.asarray([14, 0], jnp.int32)
    row, blocks, last, n_iter, first = parts.live_items(
        tables, pos, 4, 1, 12, 4, lo=lo)
    # row 0: chunks 3, 4, 5 (positions 12-23); row 1: chunks 0, 1
    assert row[:5].tolist() == [0, 0, 0, 1, 1]
    assert blocks[:5, 0].tolist() == [4, 5, 6, 13, 14]
    assert last[:5].tolist() == [9, 5, 1, 6, 2]
    assert first[:5].tolist() == [2, -2, -6, 0, -4]
    assert int(n_iter) == 2 and (np.asarray(last[5:]) == -1).all()
    plain = parts.live_items(tables, pos, 4, 1, 12, 4)
    assert len(plain) == 4 and int(plain[3]) == 2       # 6 + 2 chunks


# -- the share of the experts -----------------------------------------------------

def _uncut():
    """The tiny model with all 8 published experts held."""
    return dict(CFG, num_experts=8, expert_share={"published": 8,
                                                  "first": 0})


def test_the_shares_add_up_to_the_uncut_layer():
    """What every share's held experts add, and the shared expert once,
    is what the uncut layer's MLP gives: the guide's test of a cut by
    the chip's share."""
    whole = ref.make_params(_uncut(), SEED, dtype=jnp.float32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, 64)),
                    jnp.float32)
    kw = dict(k=2, scale=2.448)
    full, _ = ref.routed_part(u, whole, first=0, **kw)
    parts = []
    for first in (0, 2, 4, 6):
        share = dict(whole, ewi=whole["ewi"][first:first + 2],
                     ewd=whole["ewd"][first:first + 2])
        parts.append(ref.routed_part(u, share, first=first, **kw)[0])
        # the program's layer, told the same share
        spec = dataclasses.replace(SPEC, experts_first=first,
                                   experts_held=2)
        y, counts, away = experts.expert_layer(
            share, u, jnp.ones((24,), bool), spec, jnp.float32)
        assert np.abs(np.asarray(y) - np.asarray(parts[-1])).max() < TOL
        assert int(counts.sum()) + int(away) == 24 * 2
    assert np.abs(np.asarray(sum(parts)) - np.asarray(full)).max() < TOL
    assert float(jnp.abs(full).max()) > 0.1
    mlp = ref.shared_part(u, whole) + full
    assert np.abs(np.asarray(ref.shared_part(u, whole) + sum(parts))
                  - np.asarray(mlp)).max() < TOL


def test_router_bias_is_in_the_choice_not_in_the_weights():
    blk = ref.make_params(_uncut(), SEED, dtype=jnp.float32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(2).normal(size=(16, 64)),
                    jnp.float32)
    p0, e0 = ref.route(u, blk, 2, 2.448)
    pushed = dict(blk, router_bias=blk["router_bias"].at[5].add(10.0))
    p1, e1 = ref.route(u, pushed, 2, 2.448)
    assert (np.asarray(e1)[:, 0] == 5).all() and not (
        np.asarray(e0)[:, 0] == 5).all()
    assert np.allclose(np.asarray(p1).sum(-1), 2.448, atol=1e-5)
    assert float(np.asarray(p1).max()) < 2.448     # a score, not score + 10


def _old_expert_layer(blk, g, live, spec, dtype):
    """`experts.expert_layer` as it was before it learned of shares
    and sigmoid scores (PR 38)."""
    n, d = g.shape
    ne, k, f = spec.n_experts, spec.experts_per_tok, spec.expert_width
    logits = jnp.dot(g, blk["router"].astype(dtype),
                     preferred_element_type=jnp.float32)
    p, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    e = jnp.where(live[:, None], e, ne).reshape(-1)
    order = jnp.argsort(e, stable=True)
    counts = jnp.sum(e[:, None] == jnp.arange(ne)[None, :], axis=0,
                     dtype=jnp.int32)
    xs = g[order // k]
    gu = jax.lax.ragged_dot(xs, blk["ewi"].astype(dtype), counts)
    mid = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    out = jax.lax.ragged_dot(mid, blk["ewd"].astype(dtype), counts)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    out = out[inv].reshape(n, k, d).astype(jnp.float32)
    y = jnp.sum(jnp.where(live[:, None, None], out * p[..., None], 0.0),
                axis=1)
    return y.astype(dtype), counts


@pytest.mark.parametrize("n,real", [(4, 3), (16, 16), (16, 9)])
def test_the_sparse_expert_familys_layer_is_bit_for_bit_as_it_was(n, real):
    """Keye's tiny layer through the generalised `_expert_layer` and
    through the function as PR 38 had it, jitted alike: the same bits."""
    cfg = tiny_sparse_moe.CONFIG
    spec = sparse_moe_llm.lm_spec(cfg)
    blk = sparse_moe_lm.make_params(cfg, SEED, dtype=jnp.float32)["blocks"][0]
    g = jnp.asarray(np.random.default_rng(n).normal(size=(n, 64)),
                    jnp.float32)
    live = jnp.arange(n) < real
    new = jax.jit(lambda b, x, lv: experts.expert_layer(
        b, x, lv, spec, jnp.float32))(blk, g, live)
    old = jax.jit(lambda b, x, lv: _old_expert_layer(
        b, x, lv, spec, jnp.float32))(blk, g, live)
    assert np.array_equal(np.asarray(new[0]), np.asarray(old[0]))
    assert np.array_equal(np.asarray(new[1]), np.asarray(old[1]))
    assert int(new[2]) == 0 and int(new[1].sum()) == real * 2


# -- the allocator of the window pools ---------------------------------------------

def _cache(**kw):
    return PagedKVCache(**dict(dict(
        num_blocks=12, block_size=4, n_layers=1, n_kv=1, head_dim=8,
        window_layers=2, window_blocks=8, window=8), **kw))


def test_window_cap_counts_the_window_and_the_span():
    assert window_cap(8, 4, 1) == 3 and window_cap(8, 4, 4) == 4
    assert window_cap(4096, 64, 1) == 65 and window_cap(4096, 64, 2048) == 97
    # the worst alignment of a decoding row: positions 5..12 of blocks of 4
    assert len({p // 4 for p in range(5, 13)}) == 3


def test_reserve_grants_both_tables_or_neither():
    c = _cache()
    assert c.reserve(12, owner="a", window=1) == "blocks"       # 11 usable
    assert c.reserve(2, owner="a", window=8) == "window"        # 7 usable
    assert c.reserve(2, owner="a", window=1, window_peak=8) == "window"
    assert c.allocator.used == c.window_alloc.used == 0
    assert c.window_alloc.failed_allocs == 2
    blocks, slot, wtab = c.reserve(2, owner="a", window=3, window_peak=7)
    assert (blocks, slot, wtab) == ([1, 2], None, [1, 2, 3])
    assert c.stats()["window"]["admit_peak_blocks"] == 7
    c.release(blocks, slot, wtab)
    assert c.allocator.used == c.window_alloc.used == 0
    # a cache without window pools answers as it always did
    assert _cache(window_layers=0, window_blocks=0, window=0).reserve(2) \
        == ([1, 2], None)


def test_trim_frees_exactly_the_blocks_wholly_behind_the_window():
    c = _cache()
    _, _, wtab = c.reserve(1, window=4)            # positions 0..15
    assert c.trim(wtab, 8) == 0                    # query 8 sees 1..8
    assert c.trim(wtab, 11) == 1                   # sees 4..11: block 0 goes
    assert wtab == [0, 2, 3, 4] and c.window_alloc.used == 3
    assert c.trim(wtab, 14, 1) == 1                # sees 7..14: block 1 stays
    assert c.trim(wtab, 15, 1) == 2 and wtab == [0, 0, 3, 4]
    c.grow(wtab, window=True)                      # the freed block again
    assert wtab[-1] in (1, 2) and c.stats()["window_blocks_freed"] == 2
    assert c.trim(wtab, 100, 2) == 5               # no further than the table
    assert c.window_alloc.used == 0 and wtab == [0] * 5
    with pytest.raises(BackendError, match="window block"):
        for _ in range(8):
            c.grow(wtab, window=True)


def test_peak_demand_with_a_cap():
    rows = [(30, 5), (2, 9), (17, 1)]
    assert peak_demand(rows, 4) == 8 + 1 + 5
    assert peak_demand(rows, 4, cap=3) == 3 + 1 + 3
    assert peak_demand(rows, 4, held=4, cap=3) == 11
    # against a brute force over every launch
    rng = np.random.default_rng(0)
    for _ in range(50):
        rows = [(int(rng.integers(0, 60)), int(rng.integers(1, 30)))
                for _ in range(int(rng.integers(1, 6)))]
        cap = int(rng.integers(1, 8))
        brute = max(sum(min(-(-(p + j) // 4), cap) for p, k in rows
                        if k >= j) for j in range(1, 31))
        assert peak_demand(rows, 4, cap=cap) == brute


# -- through the engine ----------------------------------------------------------

def _live_window_blocks(req):
    return sum(b != 0 for b in req.window_table)


def _drain_checked(eng):
    """Step the engine dry; after every step each row's window table
    holds no more than its cap, the allocators count what the tables
    hold, and no launch is refused a block (`grow` would raise)."""
    cache = eng.cache
    caps = cache.window_cap(1), cache.window_cap(eng.prefill_chunk)
    events = []
    while eng.has_work:
        events.extend(eng.step())
        rows = eng.active + eng.prefilling
        for r in eng.active:
            assert _live_window_blocks(r) <= caps[0], r.req_id
        for r in eng.prefilling:
            assert _live_window_blocks(r) <= caps[1], r.req_id
        assert sum(map(_live_window_blocks, rows)) == cache.window_alloc.used
        assert sum(len(r.block_table) for r in rows) == cache.allocator.used
    return events


@pytest.mark.parametrize("chunk_every", [1, 3])
def test_engine_serves_the_references_tokens(bundle, params, chunk_every):
    """Greedy rows run a launch ahead, so a window block given back after
    a launch was dispatched is granted again while that launch may still
    run; the tokens are the reference's all the same."""
    eng = _engine(bundle, chunk_every=chunk_every)
    reqs = [eng.submit(_prompt(p, seed=p), max_new_tokens=n)
            for p, n in [(5, 2), (7, 6), (20, 8), (33, 5), (3, 4), (18, 9),
                         (29, 12), (4, 20)]]
    _drain_checked(eng)
    for r in reqs:
        ids = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        lg = np.asarray(ref.forward_logits(params, CFG, ids))
        lg = lg[len(r.prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() < TOL, r.req_id
    st = eng.stats()
    win = st["cache"]["window"]
    assert st["lookahead_steps"] > 0
    assert st["cache"]["window_blocks_freed"] > 0
    assert win["blocks_used"] == st["cache"]["blocks_used"] == 0
    # 4 rows at their cap of 3 and what one chunk's cap adds
    assert win["blocks_total"] == 4 * 3 + 1
    assert win["blocks_live_high_water"] <= win["admit_peak_blocks"] <= 13
    assert win["block_bytes"] == 2 * 2 * BS * 2 * 16 * 4
    assert st["cache"]["block_bytes"] == 2 * 1 * BS * 2 * 16 * 4
    assert st["admission_blocked_window"] == 0
    ex = st["executor"]
    assert ex["family"] == "window_moe"
    assert ex["expert_pairs_held"] + ex["expert_pairs_away"] > 0
    assert ex["kv_tokens_window"] < ex["kv_tokens_full"]


@pytest.mark.parametrize("seed", range(4))
def test_random_requests_never_pass_the_cap_or_meet_a_refused_grow(bundle,
                                                                   seed):
    rng = np.random.default_rng(seed)
    eng = _engine(bundle, num_blocks=int(rng.integers(20, 40)),
                  chunk_every=int(rng.integers(1, 3)))
    order = []
    for i in range(10):
        plen = int(rng.integers(1, 40))
        r = eng.submit(_prompt(plen, seed=i),
                       max_new_tokens=int(rng.integers(1, 64 - plen)),
                       eos_id=int(rng.integers(0, 256)) if i % 3 == 0
                       else None)
        order.append(r.req_id)
    events = _drain_checked(eng)
    first = []
    for e in events:
        if e.request.req_id not in first:
            first.append(e.request.req_id)
    assert eng.finished == 10
    assert eng.cache.allocator.used == eng.cache.window_alloc.used == 0
    assert sum(eng.rows[k] for k in eng.rows if k != "total") \
        == eng.rows["total"]


def test_short_of_window_blocks_is_counted_apart(bundle, monkeypatch):
    """With window pools that hold two rows' caps where four rows are
    admitted, the head of the queue waits for window blocks:
    `admission_blocked_window`, the rows' `blocked_window` and the span
    `admit_blocked_window`, not `admission_blocked`."""
    from nnstreamer_tpu.llm import families

    kw = families.WindowMoESet.cache_kw
    monkeypatch.setattr(
        families.WindowMoESet, "cache_kw",
        lambda self, n: dict(kw(self, n), window_blocks=2 * 3 + 1 + 1))
    tracer = Tracer()
    eng = _engine(bundle, tracer=tracer)
    for i in range(4):
        eng.submit(_prompt(20, seed=i), max_new_tokens=12)
    _drain_checked(eng)
    st = eng.stats()
    assert st["admission_blocked_window"] > 0 and st["admission_blocked"] == 0
    assert st["rows"]["blocked_window"] > 0 and st["rows"]["blocked"] == 0
    labels = {e[3] for e in tracer.events() if e[1] == "llm"}
    assert "admit_blocked_window" in labels and "admit_blocked" not in labels
    assert eng.finished == 4


def test_spans_say_what_a_step_and_a_chunk_read(bundle):
    tracer = Tracer()
    eng = _engine(bundle, tracer=tracer)
    # sampled rows resolve every step: the spans are written at once
    for i, p in enumerate((20, 33)):
        eng.submit(_prompt(p, seed=i), max_new_tokens=6, temperature=0.7)
    eng.drain()
    decode = [e[6] for e in tracer.events() if e[3] == "invoke"
              and e[6].get("what") == "llm_decode"]
    chunks = [e[6] for e in tracer.events() if e[3] == "invoke"
              and e[6].get("what") == "llm_prefill_chunk"]
    assert decode and chunks
    for key in ("rows", "kv_tokens_full", "kv_tokens_window", "kv_slots",
                "experts_touched", "expert_pairs_held", "expert_pairs_away"):
        assert key in decode[-1], key
    for key in ("pos0", "clen", "ctx_tiles_full", "ctx_tiles_window",
                "attend", "chunk_qblocks_clear", "chunk_qblocks_edge",
                "chunk_qblocks_skipped"):
        assert key in chunks[-1], key
    # a chunk is launched unsynced: what its read-back tells is on the
    # span that resolved it, under the chunk's own req, pos0 and clen
    resolved = [e[6] for e in tracer.events() if e[3] == "resolve"]
    assert len(resolved) == len(chunks) + sum(
        e[3] == "compile" and e[6].get("what") == "llm_prefill_chunk"
        for e in tracer.events())
    for key in ("req", "pos0", "clen", "ctx_tiles_window", "experts_touched",
                "expert_load_max", "expert_pairs_held"):
        assert key in resolved[-1], key
    last = decode[-1]
    assert last["kv_tokens_window"] <= last["rows"] * WINDOW
    assert last["expert_pairs_held"] + last["expert_pairs_away"] \
        == last["rows"] * 2 * 2                # 2 a token, 2 expert layers
    # a bucket of at most 4 rows x 2 lies inside one row tile of 64: a
    # visit a touched expert
    assert last["expert_row_tile"] == 64
    assert last["expert_tile_visits"] == last["experts_touched"]
    counters = eng.stats()["executor"]
    assert counters["expert_tile_rows"] \
        == 64 * counters["expert_tile_visits"] > 0
    admit = [e[6] for e in tracer.events() if e[1] == "llm"
             and e[3].startswith("admit")]
    assert "window_free" in admit[0]


# -- what the family refuses ------------------------------------------------------

def test_refusals(bundle, params):
    with pytest.raises(BackendError, match="paged_kernel=pallas"):
        _executor(bundle, paged_kernel="pallas")
    with pytest.raises(BackendError, match="shards"):
        LLMEngine(bundle, dtype=jnp.float32, shards=2, **POOL)
    blocks = [dict(params["blocks"][0], wqkv_scale=jnp.ones((1,)))] \
        + params["blocks"][1:]
    with pytest.raises(BackendError, match="W8A8"):
        _executor(ModelBundle(fn=None, params=dict(params, blocks=blocks),
                              lm=SPEC))
    with pytest.raises(BackendError, match="2 layers under a spec"):
        _executor(ModelBundle(fn=None, params=dict(
            params, blocks=params["blocks"][:2]), lm=SPEC))
    with pytest.raises(BackendError, match="both kinds"):
        _executor(ModelBundle(fn=None, params=params, lm=dataclasses.replace(
            SPEC, layer_kinds=("window",) * 3)))
    # a whole prompt past one chunk's reach needs chunked prefill
    eng = LLMEngine(bundle, dtype=jnp.float32, block_size=4, num_blocks=2000,
                    max_len=6000)
    with pytest.raises(BackendError, match="needs chunked prefill"):
        eng.submit(_prompt(5000), max_new_tokens=4)
