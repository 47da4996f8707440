"""The hybrid family's chunk attends its chosen blocks by a walk over the
live context (llm/hybrid_lm.py `sparse_attend_walk`): the walk against a
plain softmax over the whole table behind the same mask, the mask against
a sort, the kernel the walk runs on the chip (here in interpret mode)
against the plain update, the whole program against the reference with
the context in several tiles, and what the spans and counters say of it.

Tolerances. Float32 on the CPU; the walk and the plain softmax attend the
same slots and add float32 sums in another order: 1e-5 on outputs of
magnitude about 1. The program against the reference: `test_hybrid_lm.TOL`.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_hybrid as tiny                                      # noqa: E402
from nnstreamer_tpu.backends import pallas_ops                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.llm import hybrid_lm, parts                 # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import hybrid_lm as ref               # noqa: E402
from perfbench.runners.hybrid_llm import lm_spec                # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
# six blocks a query, a window of two: three chosen of those left
CFG_W2 = dict(CFG, assumed_sizes=dict(
    CFG["assumed_sizes"], sparse_topk=6, sparse_window_size=16))
TOL = 1e-4          # test_hybrid_lm.TOL: the program against the reference
OUT_TOL = 1e-5
BS, MB, NBLK, TILE = 4, 32, 48, 32     # a table of 128 slots: 4 tiles of 32
G, HD = SPEC.n_kv, SPEC.head_dim


def _layer_state(seed, written):
    """Pools of two sparse layers with random keys and values, a table
    whose first `written` slots have blocks of their own (the rest read
    the scratch block), and the sequence's compressed keys."""
    rng = np.random.default_rng(seed)

    def pool():
        return jnp.asarray(rng.normal(size=(2 * G, NBLK, BS, 1, HD)),
                           jnp.float32)

    tab = np.zeros((MB,), np.int32)
    n = -(-written // BS)
    tab[:n] = 1 + rng.permutation(NBLK - 1)[:n]
    ck = jnp.asarray(rng.normal(size=(G, MB, HD)), jnp.float32)
    return pool(), pool(), jnp.asarray(tab), ck


def _queries(seed, pos0, n, spec):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(n, spec.n_heads, HD)), jnp.float32)
    return q, pos0 + jnp.arange(n)


def _mask(q, qpos, ck, spec):
    qg = q.reshape(q.shape[0], G, -1, HD)
    return hybrid_lm.attended_mask(
        hybrid_lm._score_blocks(qg, qpos, ck, spec), qpos, spec)


# name: (configuration, first position, queries, slots written)
WALK_CASES = {
    "position_0": (CFG, 0, 16, 16),
    "inside_the_window": (CFG, 4, 8, 12),
    "across_a_context_tile_boundary": (CFG, 24, 16, 40),
    "a_window_of_two_blocks_and_three_chosen": (CFG_W2, 72, 16, 88),
    "the_kv_heads_choose_different_blocks": (CFG, 96, 16, 112),
    "padding_queries_past_the_last": (CFG, 96, 16, 101),
}


def _attend_whole_table(q, qpos, mask, tab, li, k_pool, v_pool, spec):
    """Every query against its table's every slot, a softmax over the
    slots of the blocks `mask` names up to the query's own position."""
    n = q.shape[0]
    head = np.asarray(hybrid_lm._head_layers(li, spec))
    k = np.asarray(k_pool)[head[:, None], np.asarray(tab)[None, :]]
    v = np.asarray(v_pool)[head[:, None], np.asarray(tab)[None, :]]
    k, v = k.reshape(G, MB * BS, HD), v.reshape(G, MB * BS, HD)
    qg = np.asarray(q).reshape(n, G, -1, HD)
    slot = np.arange(MB * BS)
    may = np.asarray(mask)[:, :, slot // spec.sel_block] & (
        slot[None, None, :] <= np.asarray(qpos)[:, None, None])
    sc = np.einsum("ngrd,gtd->ngrt", qg, k) * HD ** -0.5
    sc = np.where(may[:, :, None, :], sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("ngrt,gtd->ngrd", w, v).reshape(n, -1, HD)


@pytest.mark.parametrize("case", list(WALK_CASES))
@pytest.mark.parametrize("fused", [False, True])
def test_the_walk_equals_a_softmax_over_the_whole_table(case, fused):
    cfg, pos0, n, written = WALK_CASES[case]
    spec = lm_spec(cfg)
    k_pool, v_pool, tab, ck = _layer_state(3, written)
    q, qpos = _queries(4, pos0, n, spec)
    li = 1
    mask = _mask(q, qpos, ck, spec)
    want = _attend_whole_table(q, qpos, mask, tab, li, k_pool, v_pool, spec)
    n_tiles = parts.tile_span(pos0, n, MB * BS, TILE)[1]
    assert n_tiles == -(-(pos0 + n) // TILE)
    got = hybrid_lm.sparse_attend_walk(
        q, qpos, mask, tab, n_tiles, li, k_pool, v_pool, spec=spec,
        dtype=jnp.float32, fused=fused, tile=TILE)
    assert got.shape == want.shape == (n, spec.n_heads, HD)
    assert float(np.abs(np.asarray(got) - want).max()) < OUT_TOL
    live = np.asarray(mask) & (np.arange(mask.shape[-1])[None, None, :]
                               <= (np.asarray(qpos) // spec.sel_block
                                   )[:, None, None])
    if case == "a_window_of_two_blocks_and_three_chosen":
        assert (live.sum(-1) == 6).all()        # 1 + 2 forced, 3 chosen
    if case == "the_kv_heads_choose_different_blocks":
        assert (live[:, 0] != live[:, 1]).any()
    if case == "position_0":
        assert n_tiles == 1


@pytest.mark.parametrize("cfg", [CFG, CFG_W2], ids=["one_chosen", "three"])
def test_the_mask_names_the_blocks_a_sort_would(cfg):
    spec = lm_spec(cfg)
    rng = np.random.default_rng(5)
    qpos = jnp.asarray([0, 7, 23, 24, 40, 63, 90, 127])
    score = jnp.asarray(rng.uniform(0, 1, size=(8, G, 16)), jnp.float32)
    # ties, which go to the lower index
    score = score.at[4:, :, 2:6].set(0.5)
    sel, take = hybrid_lm.chosen_mask(score, qpos, spec)
    mask = np.asarray(hybrid_lm.attended_mask(score, qpos, spec))
    j = spec.sel_topk - spec.sel_init - spec.sel_window // spec.sel_block
    wb = spec.sel_window // spec.sel_block
    own = np.asarray(qpos) // spec.sel_block
    for n in range(8):
        forced = [b for b in range(16)
                  if b < spec.sel_init or b > own[n] - wb]
        free = [b for b in range(own[n] + 1) if b not in forced]
        assert int(take[n]) == min(j, len(free))
        for g in range(G):
            # a stable sort by falling score keeps the lower index first
            best = sorted(free, key=lambda b: -float(score[n, g, b]))[:j]
            assert list(np.flatnonzero(sel[n, g])) == sorted(best)
            assert list(np.flatnonzero(mask[n, g])) == sorted(forced + best)
    # and up to a query's own block, the decode step's selection
    every, ok = hybrid_lm.select_blocks(score, qpos, spec)
    for n in range(4):      # the rows without ties (top_k's order differs)
        for g in range(G):
            assert sorted(np.asarray(every[n, g])[np.asarray(ok[n, g])]) \
                == [b for b in np.flatnonzero(mask[n, g]) if b <= own[n]]


def test_the_kernel_equals_the_plain_update_at_16_heads_a_group():
    """`selected_block_update` as the walk calls it: one KV head, 16
    query heads a group, keys of 0 and 1 under a threshold of 0 with no
    tie taken, tile number 0 of a key array one tile wide."""
    rng = np.random.default_rng(6)
    c, grp, tile = 16, 16, 32
    qg = jnp.asarray(rng.normal(size=(c, 1, grp, HD)), jnp.float32)
    kt = jnp.asarray(rng.normal(size=(tile, 1, HD)), jnp.float32)
    vt = jnp.asarray(rng.normal(size=(tile, 1, HD)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2, size=(c, tile)), jnp.uint32)
    keys = keys.at[3].set(0)                       # selects nothing
    t, cut = jnp.zeros((c,), jnp.uint32), jnp.full((c,), -1, jnp.int32)
    state = (jnp.asarray(rng.normal(size=(1, grp, c)), jnp.float32),
             jnp.asarray(rng.uniform(1, 9, size=(1, grp, c)), jnp.float32),
             jnp.asarray(rng.normal(size=(1, grp, c, HD)), jnp.float32))
    want = parts.attend_plain(qg, kt, vt, keys, t, cut, 0, state)
    got = pallas_ops.selected_block_update(
        qg.transpose(1, 2, 0, 3), kt, vt, keys, t, cut, 0, *state, block_q=8)
    for w, g_, s in zip(want, got, state):
        assert g_.shape == w.shape
        assert float(jnp.abs(g_ - w).max()) < OUT_TOL
        assert (np.asarray(g_)[:, :, 3] == np.asarray(s)[:, :, 3]).all()


# -- the whole program, its context in several tiles --------------------------

@pytest.fixture(scope="module")
def params():
    return ref.make_params(CFG, 2**31 + 5, dtype=jnp.float32)


def _executor(params, cfg=CFG, **kw):
    return PagedLLMExecutor(
        ModelBundle(fn=None, params=params, lm=lm_spec(cfg)),
        dtype=jnp.float32, state_slots=4, block_size=4, num_blocks=80,
        max_len=64, **kw)


# tiles of 16 slots: the chunks at 0, 16 and 32 cover 1, 2 and 3; of 32: 1,
# 1 and 2; and one tile that holds the table
@pytest.mark.parametrize("tile,tiles", [(16, [1, 2, 3]), (32, [1, 1, 2]),
                                        (64, [1, 1, 1])])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("cfg", [CFG, CFG_W2], ids=["w1", "w2"])
def test_prefill_then_decode_equals_one_forward_pass(
        params, monkeypatch, cfg, fused, tile, tiles):
    # the tile is the program's argument (`HybridSet.chunk_kw`), so a
    # program traced under another is not met again
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    if fused:
        monkeypatch.setattr(parts, "fused_attend",
                            lambda c, tile, hd: True)
    ids = np.random.default_rng(41).integers(0, 256, 49).astype(np.int32)
    want = np.asarray(ref.forward_logits(params, cfg, ids, q_block=8))
    tracer = Tracer(max_events=4096)
    ex = _executor(params, cfg, tracer=tracer, name="llm")
    assert ex.programs.chunk_kw(0, 16)["fused"] is fused
    blocks, slot = ex.cache.reserve(ex.cache.blocks_for(len(ids)))
    # the bucket's first call compiles and says nothing of the chunk
    ex.prefill_chunk(ids[:16], 0, blocks, bucket=16, state_slot=slot)
    before = ex.stats()
    for at in (0, 16, 32):      # the last chunk 9 tokens: padding queries
        got = ex.prefill_chunk(ids[at:min(at + 16, 41)], at, blocks,
                               bucket=16, state_slot=slot, req="r")
    assert np.abs(got - want[40]).max() < TOL
    for t in range(41, 49):
        got = ex.decode([int(ids[t])], [blocks], [t], state_slots=[slot])
        assert np.abs(got[0] - want[t]).max() < TOL
    spans = [a for ph, cat, _, label, _, _, a in tracer.events()
             if ph == "X" and cat == "backend" and a
             and a.get("req") == "r" and "ctx_tiles" in a]
    assert [a["ctx_tiles"] for a in spans] == tiles
    assert [a["kv_slots"] for a in spans] == [n * tile for n in tiles]
    assert all("attend" not in a for a in spans)
    after = ex.stats()
    assert after["chunk_tiles_attended"] - before["chunk_tiles_attended"] \
        == sum(tiles)
    assert after["chunk_prefills"] - before["chunk_prefills"] == 3


# -- how the update is chosen, and what the host says of the walk -------------

def test_the_update_is_chosen_from_backend_and_shapes_alone(params,
                                                            monkeypatch):
    ps = _executor(params).programs
    assert jax.default_backend() == "cpu"
    assert ps.chunk_kw(0, 2048)["fused"] is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ps.chunk_kw(0, 2048)["fused"] is False   # a head of 16 values
    ps.head_dim = 128
    assert ps.chunk_kw(0, 2048)["fused"] is True
    assert ps.chunk_kw(0, 64)["fused"] is True      # a short bucket
    assert ps.chunk_kw(0, 8) == dict(
        spec=ps.spec, dtype=jnp.float32, by_block=True, fused=True,
        tile=parts.CTX_TILE)


def test_ctx_tiles_is_the_programs_own_trip_count(params, monkeypatch):
    """`note_chunk` and `_chunk_sparse` ask one function: tiles up to the
    bucket's last padded row, capped at the table's; on the host's ints
    and on the program's traced position."""
    monkeypatch.setattr(parts, "CTX_TILE", 16)
    ps = _executor(params).programs               # max_len 64: 4 tiles
    assert ps.chunk_kw(0, 8)["tile"] == 16
    cases = ((0, 8), (8, 5), (9, 8), (40, 8), (60, 4), (62, 2))
    said = [ps.note_chunk(pos0, clen, 8) for pos0, clen in cases]
    assert [s["ctx_tiles"] for s in said] == [1, 1, 2, 3, 4, 4]
    traced = jax.jit(lambda p: parts.tile_span(p, 8, 64, 16)[1])
    for (pos0, _), s in zip(cases, said):
        assert int(traced(jnp.int32(pos0))) == s["ctx_tiles"]
    assert ps.counters["chunk_tiles_attended"] == 15
    # what the walk reads, a KV head: its tiles' slots, once
    assert [s["kv_slots"] for s in said] == [16, 16, 32, 48, 64, 64]
    assert ps.counters["kv_slots_read"] == 240
    assert all("attend" not in s for s in said)
