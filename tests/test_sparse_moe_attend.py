"""The sparse-expert chunk's attention walk (llm/sparse_moe.py): the fused
tile update (`backends/pallas_ops.selected_block_update`, here in
interpret mode) against the plain one (`sparse_moe.attend_plain`) on the
same inputs, the whole chunk program with the fused update forced against
the plain reference, and how the update is chosen and reported.

Tolerances. Both updates compute in float32 here and differ only in the
order float32 sums are added inside a tile: 1e-5 absolute on carries of
magnitude about 1 (measured 2.9e-6).
The chunk program against the reference: `test_sparse_moe.TOL`.
"""

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
from nnstreamer_tpu.llm import sparse_moe                       # noqa: E402
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
    want = sparse_moe.attend_plain(
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
    monkeypatch.setattr(sparse_moe, "fused_attend", lambda c, tile, hd: True)


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
    monkeypatch.setattr(sparse_moe, "_CTX_TILE", 16)
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
    assert not sparse_moe.fused_attend(2048, 1024, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sparse_moe.fused_attend(2048, 1024, 128)
    assert sparse_moe.fused_attend(64, 1024, 128)       # a short bucket
    assert not sparse_moe.fused_attend(2048, 1024, 64)  # half a lane tile
    assert not sparse_moe.fused_attend(2048, 1000, 128)   # nor a tile
    assert not sparse_moe.fused_attend(
        sparse_moe._FUSED_Q_BLOCK + 8, 1024, 128)


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
    monkeypatch.setattr(sparse_moe, "_CTX_TILE", 16)
    ps = _executor(params).programs               # max_len 64: 4 tiles
    said = [ps.note_chunk(pos0, clen, 8)["ctx_tiles"]
            for pos0, clen in ((0, 8), (8, 5), (9, 8), (40, 8), (60, 4))]
    assert said == [1, 1, 2, 3, 4]
    assert ps.counters["chunk_tiles_attended"] == sum(said)
