"""The sparse-expert chunk's attention walk (llm/sparse_moe.py): the fused
tile update (`backends/pallas_ops.selected_block_update`, here in
interpret mode) against the plain one (`parts.attend_plain`) on the
same inputs, the whole chunk program with the fused update forced against
the plain reference, and how the update is chosen and reported; and the
tile update's causal form (`pallas_ops.causal_block_update`, the window
and the latent family's) against both, with the arithmetic that names
what a block of its queries does (`pallas_ops.block_reach`).

Tolerances. Both updates compute in float32 here and differ only in the
order float32 sums are added inside a tile: 1e-5 absolute on carries of
magnitude about 1 (measured 2.9e-6).
The chunk program against the reference: `test_sparse_moe.TOL`.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_sparse_moe as tiny                                  # noqa: E402
from nnstreamer_tpu.backends import pallas_ops                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.llm import parts, sparse_moe, window_moe    # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import sparse_moe_lm as ref           # noqa: E402
from perfbench.runners.sparse_moe_llm import lm_spec            # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
TOL = 2e-5          # test_sparse_moe.TOL: the chunk program's
CARRY_TOL = 1e-5
C, TILE, HD = 16, 32, 16
T_KEY = np.uint32(5 << 28)


def _tile_case(case, rng):
    """(nkv, grp, tile number j, keys (C, 3 * TILE), t, cut, filled)."""
    nkv, grp, j, filled = 2, 4, 1, True
    keys = (rng.integers(1, 9, size=(C, 3 * TILE)).astype(np.uint32) << 28)
    t = np.full((C,), T_KEY, np.uint32)
    cut = np.full((C,), 3 * TILE, np.int32)        # no tie is cut
    if case == "tie_straddles_cut":
        # every other slot of the tile ties with T; the cut falls inside
        # the tile at a different slot for every query, and for the last
        # two before and after it
        keys[:, TILE:2 * TILE:2] = T_KEY
        cut = (TILE + np.arange(C) * 2 + 1).astype(np.int32)
        cut[-2:] = (TILE - 3, 2 * TILE + 5)
    elif case in ("first_tile_selects_nothing", "later_tile_selects_nothing"):
        # queries 3 and 7: no key of the tile reaches T, and the ties
        # that would lie past the cut
        j = 0 if case.startswith("first") else 2
        filled = j > 0
        keys[3, j * TILE:(j + 1) * TILE] = np.uint32(1 << 28)
        keys[7, j * TILE:(j + 1) * TILE] = T_KEY
        cut[7] = j * TILE - 1
    elif case == "tile_past_a_short_query":
        # queries at positions 0..15: key 0 from their position on, as
        # `score_tile` leaves the slots a query may not attend
        j = 1
        keys[np.arange(3 * TILE)[None, :] > np.arange(C)[:, None]] = 0
    elif case == "grp1":
        nkv, grp = 3, 1
    elif case == "grp8":
        nkv, grp = 1, 8
    elif case == "empty_carry":
        j, filled = 0, False
    else:
        assert case == "carry_filled"
    return nkv, grp, j, keys, t, cut, filled


def _carry(rng, nkv, grp, filled, hd=HD):
    if not filled:
        return (jnp.full((nkv, grp, C), -1e30, jnp.float32),
                jnp.zeros((nkv, grp, C), jnp.float32),
                jnp.zeros((nkv, grp, C, hd), jnp.float32))
    return (jnp.asarray(rng.normal(size=(nkv, grp, C)), jnp.float32),
            jnp.asarray(rng.uniform(1, 9, size=(nkv, grp, C)), jnp.float32),
            jnp.asarray(rng.normal(size=(nkv, grp, C, hd)), jnp.float32))


def _both(qg, kt, vt, keys, t, cut, j, state, **blocks):
    tile = kt.shape[0]
    keys, t, cut = jnp.asarray(keys), jnp.asarray(t), jnp.asarray(cut)
    want = parts.attend_plain(
        qg, kt, vt, keys[:, j * tile:(j + 1) * tile], t, cut, j * tile,
        state)
    got = pallas_ops.selected_block_update(
        qg.transpose(1, 2, 0, 3), kt, vt, keys, t, cut, jnp.int32(j),
        *state, **blocks)
    return want, got


TILE_CASES = ["tie_straddles_cut", "first_tile_selects_nothing",
              "later_tile_selects_nothing", "tile_past_a_short_query",
              "grp1", "grp8", "empty_carry", "carry_filled"]


@pytest.mark.parametrize("case", TILE_CASES)
def test_fused_tile_update_equals_the_plain_one(case):
    rng = np.random.default_rng(TILE_CASES.index(case))
    nkv, grp, j, keys, t, cut, filled = _tile_case(case, rng)
    qg = jnp.asarray(rng.normal(size=(C, nkv, grp, HD)), jnp.float32)
    kt = jnp.asarray(rng.normal(size=(TILE, nkv, HD)), jnp.float32)
    vt = jnp.asarray(rng.normal(size=(TILE, nkv, HD)), jnp.float32)
    state = _carry(rng, nkv, grp, filled)
    # two blocks of queries: the kernel's own grid
    want, got = _both(qg, kt, vt, keys, t, cut, j, state,
                      block_q=8)
    for w, g in zip(want, got):
        assert g.shape == w.shape and g.dtype == jnp.float32
        assert float(jnp.abs(g - w).max()) < CARRY_TOL
    if "selects_nothing" in case or case == "tile_past_a_short_query":
        # a query the tile selects nothing for keeps its carry, bit for
        # bit: no exp(0) of a masked score leaks into l
        idle = [3, 7] if "selects_nothing" in case else list(range(C))
        for s, g in zip(state, got):
            assert (np.asarray(g)[:, :, idle] == np.asarray(s)[:, :, idle]
                    ).all()


@pytest.mark.parametrize("case", TILE_CASES[:4])
def test_fused_update_attends_exactly_the_selected_slots(case):
    """Queries of zero and one-hot values: from an empty carry, acc is the
    mask itself (every selected slot weighs exp(0) = 1), so one slot
    masked differently is one value off by 1."""
    rng = np.random.default_rng(11)
    nkv, grp, j, keys, t, cut, _ = _tile_case(case, rng)
    qg = jnp.zeros((C, nkv, grp, TILE), jnp.float32)
    kt = jnp.asarray(rng.normal(size=(TILE, nkv, TILE)), jnp.float32)
    vt = jnp.broadcast_to(jnp.eye(TILE)[:, None, :], (TILE, nkv, TILE))
    state = _carry(rng, nkv, grp, False, hd=TILE)
    want, got = _both(qg, kt, vt, keys, t, cut, j, state,
                      block_q=8)
    key_t = keys[:, j * TILE:(j + 1) * TILE]
    slot = j * TILE + np.arange(TILE)[None, :]
    sel = (key_t > t[:, None]) | ((key_t == t[:, None])
                                  & (slot <= cut[:, None]))
    for m, l, acc in (want, got):
        assert (np.asarray(acc) == sel[None, None].astype(np.float32)).all()
        assert (np.asarray(l) == sel.sum(1)[None, None]).all()
        assert (np.asarray(m) == np.where(
                sel.any(1), 0.0, -1e30).astype(np.float32)).all()
    if case == "tie_straddles_cut":
        # the cut before the tile takes no tie, the cut past it all
        assert not sel[-2, ::2].any() and sel[-1, ::2].all()
        assert [int(n) for n in sel[:3, ::2].sum(1)] == [1, 2, 3]


def test_fused_update_refuses_blocks_that_do_not_tile():
    rng = np.random.default_rng(0)
    qg = jnp.zeros((C, 1, 1, HD))
    kt = jnp.zeros((TILE, 1, HD))
    state = _carry(rng, 1, 1, False)
    keys = np.zeros((C, 2 * TILE), np.uint32)
    with pytest.raises(ValueError, match="divisible"):
        _both(qg, kt, kt, keys, np.zeros((C,), np.uint32),
              np.zeros((C,), np.int32), 0, state, block_q=12)


# -- the causal form: the mask from positions, inside the kernel --------------

CC, CTILE = 32, 32          # queries and slots of the small cases


@functools.lru_cache(maxsize=None)
def _causal_fn(window, block_q):
    return jax.jit(lambda qh, kt, vt, pos0, slot0, m, l, acc:
                   pallas_ops.causal_block_update(
                       qh, kt, vt, pos0, slot0, m, l, acc, window=window,
                       block_q=block_q))


@functools.lru_cache(maxsize=None)
def _selected_fn(block_q):
    return jax.jit(lambda qh, kt, vt, keys, m, l, acc:
                   pallas_ops.selected_block_update(
                       qh, kt, vt, keys, jnp.zeros((keys.shape[0],),
                                                   jnp.uint32),
                       jnp.full((keys.shape[0],), -1, jnp.int32), 0,
                       m, l, acc, block_q=block_q))


_plain_fn = jax.jit(parts.attend_tile_plain, static_argnums=(5,))


def _seen(pos0, c, first, tile, window):
    """(c, tile) bool: query pos0 + i sees slot first + s."""
    q = pos0 + np.arange(c)[:, None]
    s = first + np.arange(tile)[None, :]
    return (s <= q) & ((s > q - window) if window else True)


def _causal_case(name):
    """-> (nkv, grp, c, tile, d, dv, pos0, window, block_q, dtype)"""
    if name.startswith("rule"):
        # the blocks the rule gives, at a head's real widths: a latent
        # head (a group of one, K 256 and V 128 wide) 1,024, 512 and 256,
        # a short chunk whole, Trinity's group of 6 and Keye's of 8 128
        geo = {"rule1024": (1, 1, 2048, 256, 128, 1024),
               "rule512": (2, 1, 512, 256, 128, 512),
               "rule256": (2, 1, 256, 256, 128, 256),
               "rule_short": (2, 1, 64, 256, 128, 64),
               "rule_grp6": (1, 6, 256, 128, 128, 128),
               "rule_grp8": (1, 8, 128, 128, 128, 128)}[name]
        nkv, grp, c, d, dv, bq = geo
        assert pallas_ops.causal_block_q(c, grp) == bq
        return nkv, grp, c, 128, d, dv, 128 + c // 2, 0, 0, jnp.float32
    at, win, group, dt = name.split("-")
    pos0 = {"p0": 0, "p1024": CTILE, "p3072": 3 * CTILE, "podd": 45}[at]
    window = {"full": 0, "wsmall": 20, "wwide": 80}[win]
    nkv, grp, d, dv = {"grp1": (3, 1, 32, 16), "grp6": (1, 6, 16, 16),
                       "grp8": (2, 8, 16, 16)}[group]
    return (nkv, grp, CC, CTILE, d, dv, pos0, window, 8,
            jnp.float32 if dt == "f32" else jnp.bfloat16)


CAUSAL_CASES = [f"{at}-{win}-{group}-f32"
                for at in ("p0", "p1024", "p3072", "podd")
                for win in ("full", "wsmall", "wwide")
                for group in ("grp1", "grp6", "grp8")] + [
    f"podd-{win}-{group}-bf16" for win in ("full", "wsmall", "wwide")
    for group in ("grp1", "grp6", "grp8")] + [
    "rule1024", "rule512", "rule256", "rule_short", "rule_grp6",
    "rule_grp8"]


@pytest.mark.parametrize("case", CAUSAL_CASES)
def test_causal_tile_update_equals_the_selected_and_the_plain_one(case):
    """Every tile from the first to one past the last query's, so that
    tiles no query reaches, tiles every query sees whole and both edges
    come by; the queries past the chunk's real ones are positions like
    any other. The selected form, given the same mask as keys of 1 and 0,
    runs the masked body everywhere; the causal form runs it on the edge
    blocks (bit for bit in float32), hands a block that sees nothing
    through, bit for bit, and runs the body with no mask where a block
    sees every slot: the same sums, which the interpreter's fusions round
    a float32 ulp apart."""
    nkv, grp, c, tile, d, dv, pos0, window, bq, dt = _causal_case(case)
    rng = np.random.default_rng(CAUSAL_CASES.index(case))
    bq_eff = bq or pallas_ops.causal_block_q(c, grp)
    qg = jnp.asarray(rng.normal(size=(c, nkv, grp, d)), dt)
    qh = qg.transpose(1, 2, 0, 3)
    kinds = set()
    for j in range(-(-(pos0 + c) // tile) + 1):
        kt = jnp.asarray(rng.normal(size=(tile, nkv, d)), dt)
        vt = jnp.asarray(rng.normal(size=(tile, nkv, dv)), dt)
        state = (jnp.asarray(rng.normal(size=(nkv, grp, c)), jnp.float32),
                 jnp.asarray(rng.uniform(1, 9, size=(nkv, grp, c)),
                             jnp.float32),
                 jnp.asarray(rng.normal(size=(nkv, grp, c, dv)),
                             jnp.float32))
        seen = _seen(pos0, c, j * tile, tile, window)
        got = _causal_fn(window, bq)(qh, kt, vt, jnp.int32(pos0),
                                     jnp.int32(j * tile), *state)
        sel = _selected_fn(min(bq_eff, 128))(
            qh, kt, vt, jnp.asarray(seen.astype(np.uint32)), *state)
        plain = _plain_fn(qg, kt, vt, pos0 + jnp.arange(c), j * tile,
                          window, state)
        # bfloat16: an ulp of a probability may round its cast the other
        # way, so only float32 is held to the bit
        exact = dt == jnp.float32
        tol = CARRY_TOL if exact else 2e-2
        for g, w, pl_, st in zip(got, sel, plain, state):
            g, w, st = np.asarray(g), np.asarray(w), np.asarray(st)
            assert g.shape == w.shape and g.dtype == np.float32
            assert np.abs(g - np.asarray(pl_)).max() < tol
            for i in range(c // bq_eff):
                rows = slice(i * bq_eff, (i + 1) * bq_eff)
                block = seen[rows]
                if not block.any():
                    kinds.add("skipped")
                    assert (g[:, :, rows] == st[:, :, rows]).all()
                a, b = g[:, :, rows], w[:, :, rows]
                if not block.all():
                    kinds.add("edge" if block.any() else "skipped")
                    assert (a == b).all() if exact else \
                        np.abs(a - b).max() < tol
                else:
                    kinds.add("clear")
                    assert np.abs(a - b).max() < tol
            # a query that sees no slot of the tile keeps its carry
            idle = ~seen.any(1)
            assert (g[:, :, idle] == st[:, :, idle]).all()
    assert "skipped" in kinds and ("edge" in kinds or "clear" in kinds)


def test_causal_update_attends_exactly_the_slots_positions_allow():
    """Queries of zero and one-hot values from an empty carry, as the
    selected form's test: acc is the mask itself."""
    c, tile, pos0, window = 32, 32, 45, 20
    qh = jnp.zeros((1, 2, c, tile), jnp.float32)
    kt = jnp.asarray(np.random.default_rng(3).normal(size=(tile, 1, tile)),
                     jnp.float32)
    vt = jnp.eye(tile)[:, None, :]
    for j in range(4):
        m, l, acc = pallas_ops.causal_block_update(
            qh, kt, vt, pos0, j * tile, jnp.full((1, 2, c), -1e30),
            jnp.zeros((1, 2, c)), jnp.zeros((1, 2, c, tile)), window=window,
            block_q=8)
        seen = _seen(pos0, c, j * tile, tile, window)
        assert (np.asarray(acc) == seen[None, None].astype(np.float32)).all()
        assert (np.asarray(l) == seen.sum(1)[None, None]).all()
        assert (np.asarray(m) == np.where(seen.any(1), 0.0, -1e30).astype(
            np.float32)).all()


def test_causal_update_refuses_blocks_that_do_not_tile():
    state = _carry(np.random.default_rng(0), 1, 1, False)
    with pytest.raises(ValueError, match="divisible"):
        pallas_ops.causal_block_update(
            jnp.zeros((1, 1, C, HD)), jnp.zeros((TILE, 1, HD)),
            jnp.zeros((TILE, 1, HD)), 0, 0, *state, block_q=12)


@pytest.mark.parametrize("window", [0, 5, 16, 40])
def test_block_reach_against_a_loop_over_every_pair(window):
    """`block_reach` for blocks of 8 queries and tiles of 16 slots, every
    first position up to 70: skip is "no (query, slot) pair is seen",
    clear "every pair is", by a loop in plain Python; numpy's arrays
    answer as the ints do."""
    bq, sk = 8, 16
    for q0 in range(0, 70):
        for s0 in range(0, 96, sk):
            pairs = [s <= q and (not window or s > q - window)
                     for q in range(q0, q0 + bq) for s in range(s0, s0 + sk)]
            skip, clear = pallas_ops.block_reach(q0, bq, s0, sk, window)
            assert (bool(skip), bool(clear)) == (not any(pairs), all(pairs))
    q0 = np.arange(0, 70)[:, None]
    s0 = np.arange(0, 96, sk)[None, :]
    skip, clear = pallas_ops.block_reach(q0, bq, s0, sk, window)
    assert skip.shape == clear.shape == (70, 6)
    assert not (skip & clear).any() and skip.any()
    # a window narrower than a block and a tile together clears no pair
    assert clear.any() == (window in (0, 40))
    assert skip[5, 1] and clear[69, 0] == (window == 0)


def test_a_programs_queries_follow_the_groups_size():
    """`causal_block_q`: 1,024 rows of queries a program (heads of the
    group x queries) or the most under it, a power of two that divides
    the chunk, never under 128, a shorter chunk whole."""
    q = pallas_ops.causal_block_q
    assert q(2048, 1) == 1024 and q(1024, 1) == 1024      # a latent head
    assert q(512, 1) == 512 and q(4096, 1) == 1024
    assert q(2048, 6) == 128 and q(1024, 6) == 128        # Trinity's group
    assert q(2048, 8) == 128 and q(2048, 16) == 128
    assert q(2048, 2) == 512 and q(2048, 4) == 256
    assert q(256, 1) == 256 and q(64, 1) == 64 and q(64, 6) == 64
    assert q(384, 1) == 128 and q(768, 1) == 256
    for c in (64, 128, 384, 1024, 2048):
        for grp in (1, 2, 4, 6, 8, 16):
            assert c % q(c, grp) == 0


# -- the whole chunk program with the fused update forced ---------------------

@pytest.fixture(scope="module")
def params():
    return ref.make_params(CFG, 2**31 + 5, dtype=jnp.float32)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 44).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward_logits(params, CFG, ids, q_block=4))


@pytest.fixture
def forced(monkeypatch):
    """The predicate says yes whatever the backend and the shapes; the
    kernel then runs in interpret mode."""
    monkeypatch.setattr(parts, "fused_attend", lambda c, tile, hd: True)


def _executor(params, **kw):
    return PagedLLMExecutor(
        ModelBundle(fn=None, params=params, lm=SPEC), dtype=jnp.float32,
        block_size=8, num_blocks=40, max_len=64, **kw)


def _prefill(ex, ids, plen, chunk, req=None):
    """ids[:plen] in chunks of `chunk`: the last chunk's logits."""
    table = ex.cache.allocator.alloc(ex.cache.blocks_for(len(ids) + 1))
    for pos in range(0, plen, chunk):
        lg = ex.prefill_chunk(ids[pos:min(pos + chunk, plen)], pos, table,
                              bucket=chunk, req=req)
    return lg, table


# prompts on both sides of topk (8) and of the chunk (8); 32: one chunk
# over the whole prompt
@pytest.mark.parametrize("plen,chunk", [(5, 8), (13, 8), (29, 8), (29, 32)])
def test_chunk_program_with_the_fused_update_equals_the_reference(
        params, ids, want, forced, plen, chunk):
    ex = _executor(params)
    lg, table = _prefill(ex, ids, plen, chunk)
    assert np.abs(lg - want[plen - 1]).max() < TOL
    assert ex.stats()["chunk_tiles_fused"] > 0
    # and the decode step reads what the chunks wrote
    nxt = ex.decode([int(ids[plen])], [table], [plen])[0]
    assert np.abs(nxt - want[plen]).max() < TOL


def test_fused_update_over_a_tiled_context(params, ids, want, forced,
                                           monkeypatch):
    """The context walked in four tiles of 16 slots, so that the carry
    passes from call to call and tiles past a query's position come
    last: chunked equals unchunked equals the reference."""
    monkeypatch.setattr(parts, "CTX_TILE", 16)
    one, _ = _prefill(_executor(params), ids, 29, 32)
    ex = _executor(params)
    chunked, _ = _prefill(ex, ids, 29, 8)
    assert np.abs(one - chunked).max() < TOL
    assert np.abs(chunked - want[28]).max() < TOL
    # chunks at 0, 8, 16, 24 cover 1, 1, 2, 2 tiles of 16 slots
    assert ex.stats()["chunk_tiles_fused"] == 6


# -- how the update is chosen, and what says so -------------------------------

def test_the_choice_is_made_from_backend_and_shapes_alone(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert not parts.fused_attend(2048, 1024, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert parts.fused_attend(2048, 1024, 128)
    assert parts.fused_attend(64, 1024, 128)       # a short bucket
    assert not parts.fused_attend(2048, 1024, 64)  # half a lane tile
    assert not parts.fused_attend(2048, 1000, 128)   # nor a tile
    assert not parts.fused_attend(
        parts.FUSED_Q_BLOCK + 8, 1024, 128)


def _chunk_spans(tracer, req):
    return [(label, args) for ph, cat, _, label, _, _, args
            in tracer.events() if ph == "X" and cat == "backend" and args
            and args.get("req") == req]


def _serve_traced(params, ids, req):
    tracer = Tracer(max_events=4096)
    ex = _executor(params, tracer=tracer, name="llm")
    _prefill(ex, ids, 13, 8)                      # compiles
    before = ex.stats()
    table = ex.cache.allocator.alloc(8)
    ex.prefill_chunk(ids[:8], 0, table, bucket=8, req=req)
    ex.prefill_chunk(ids[8:13], 8, table, bucket=8, sync=False, req=req)
    ex.decode([int(ids[13])], [table], [13])      # its sync resolves it
    after = ex.stats()
    return _chunk_spans(tracer, req), {
        k: after[k] - before[k]
        for k in ("chunk_tiles_attended", "chunk_tiles_fused")}


def test_on_the_cpu_the_spans_and_counters_say_plain(params, ids):
    spans, counted = _serve_traced(params, ids, "p")
    assert [label for label, _ in spans] == ["invoke", "invoke", "resolve"]
    assert all(a["attend"] == "plain" and a["ctx_tiles"] == 1
               for _, a in spans)
    assert counted == {"chunk_tiles_attended": 2, "chunk_tiles_fused": 0}


def test_with_the_predicate_forced_they_say_fused(params, ids, forced):
    spans, counted = _serve_traced(params, ids, "f")
    assert [label for label, _ in spans] == ["invoke", "invoke", "resolve"]
    assert all(a["attend"] == "fused" and a["ctx_tiles"] == 1
               for _, a in spans)
    assert spans[2][1]["pos0"] == 8 and "expert_load_max" in spans[2][1]
    assert counted == {"chunk_tiles_attended": 2, "chunk_tiles_fused": 2}


def test_ctx_tiles_is_the_programs_own_count(params, monkeypatch):
    """`note_chunk` against `sparse_moe_prefill_chunk`'s `n_tiles`: tiles
    up to the chunk's last padded row, capped at the table's."""
    monkeypatch.setattr(parts, "CTX_TILE", 16)
    ps = _executor(params).programs               # max_len 64: 4 tiles
    said = [ps.note_chunk(pos0, clen, 8)["ctx_tiles"]
            for pos0, clen in ((0, 8), (8, 5), (9, 8), (40, 8), (60, 4))]
    assert said == [1, 1, 2, 3, 4]
    assert ps.counters["chunk_tiles_attended"] == sum(said)
