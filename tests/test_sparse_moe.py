"""The sparse-expert family (llm/sparse_moe.py) through the executor and
the pools, against the one plain reference
(perfbench/references/sparse_moe_lm.py) on seeded weights at a tiny size.

Tolerances. Program and reference both compute in float32 here (the CPU
does no bfloat16 rounding inside a float32 product), so logits agree to
the reassociation of float32 sums: 2e-5 absolute on logits of magnitude
about 1 (measured 1.3e-6). Computing in bfloat16 moves them by 1e-2.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_sparse_moe as tiny                                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import experts, parts, sparse_moe       # noqa: E402
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.families import SparseMoESet            # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import sparse_moe_lm as ref           # noqa: E402
from perfbench.runners.sparse_moe_llm import lm_spec            # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
TOL = 2e-5
CHUNK, TOPK = 8, 8


@pytest.fixture(scope="module")
def params():
    return ref.make_params(CFG, 2**31 + 5, dtype=jnp.float32)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 44).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, ids):
    taps = {}
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.forward_logits(params, CFG, ids, q_block=4,
                                               taps=taps))
    return logits, taps


def _executor(params, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 40)
    kw.setdefault("max_len", 64)
    return PagedLLMExecutor(ModelBundle(fn=None, params=params, lm=SPEC),
                            **kw)


def _serve(ex, ids, plen, chunk=CHUNK):
    """Chunked prefill of ids[:plen], then one decode step a further
    token: the logits of positions plen-1 .. len(ids)-1."""
    table = ex.cache.allocator.alloc(ex.cache.blocks_for(len(ids) + 1))
    out, pos = [], 0
    while pos < plen:
        part = ids[pos:min(pos + chunk, plen)]
        lg = ex.prefill_chunk(part, pos, table, bucket=chunk)
        pos += len(part)
    out.append(lg)
    for t in range(plen, len(ids)):
        out.append(ex.decode([int(ids[t])], [table], [t])[0])
    return np.stack(out)


# prompts on both sides of topk (8) and of the chunk (8)
@pytest.mark.parametrize("plen", [5, 8, 13, 29])
def test_chunked_prefill_then_decode_equals_the_reference(params, ids, want,
                                                          plen):
    got = _serve(_executor(params), ids, plen)
    assert np.abs(got - want[0][plen - 1:]).max() < TOL


def test_chunks_that_do_not_lie_on_whole_blocks(params, ids, want):
    """Blocks of 16 slots under chunks of 8: every second chunk starts
    in the middle of a block, so the slots are written one by one."""
    ex = _executor(params, block_size=16, num_blocks=24)
    assert ex.cache.idx.shape == (2, 24, 1, 128)
    got = _serve(ex, ids, 29)
    assert np.abs(got - want[0][28:]).max() < TOL


def test_chunked_equals_unchunked_and_a_tiled_context(params, ids, want,
                                                      monkeypatch):
    """One chunk over the whole prompt, chunks of 8, and the same with
    the context walked in four tiles of 16 slots: the same logits."""
    whole = _executor(params)
    table = whole.cache.allocator.alloc(8)
    one = whole.prefill(ids[:29], table)
    chunked = _serve(_executor(params), ids, 29)
    monkeypatch.setattr(parts, "CTX_TILE", 16)
    tiled = _serve(_executor(params), ids, 29)
    assert np.abs(one - chunked[0]).max() < TOL
    assert np.abs(tiled - chunked).max() < TOL
    assert np.abs(tiled - want[0][28:]).max() < TOL


def test_a_batch_of_rows_at_different_depths(params, ids, want):
    ex = _executor(params)
    tables = [ex.cache.allocator.alloc(8) for _ in range(3)]
    for table, plen in zip(tables, (5, 13, 29)):
        ex.prefill(ids[:plen], table)
    lg = ex.decode([int(ids[5]), int(ids[13]), int(ids[29])], tables,
                   [5, 13, 29])
    for row, t in enumerate((5, 13, 29)):
        assert np.abs(lg[row] - want[0][t]).max() < TOL


def _selected(keys, t, p):
    pos = np.arange(keys.shape[1])[None, :]
    return (keys > t[:, None]) | ((keys == t[:, None]) & (pos <= p[:, None]))


def test_selection_is_exact_and_ties_go_to_the_lower_position():
    """Both selections against the reference's on scores full of ties
    (among them +0.0 and -0.0, which compare equal)."""
    rng = np.random.default_rng(3)
    s, tile = 48, 16
    scores = rng.choice(np.asarray([-1.5, -0.0, 0.0, 0.25, 0.25, 2.0, 3.5],
                                   np.float32), size=(24, s))
    scores[:4] = rng.normal(size=(4, s)).astype(np.float32)
    qpos = np.asarray(list(range(12)) + list(range(30, 42)), np.int32)
    want = np.asarray(ref.selection_mask(jnp.asarray(scores),
                                         jnp.asarray(qpos), TOPK))
    assert (want.sum(1) == np.minimum(TOPK, qpos + 1)).all()
    # the chunk program's: integer keys, a threshold and a position cut
    may = np.arange(s)[None, :] <= qpos[:, None]
    keys = jnp.where(may, parts.sort_keys(jnp.asarray(scores)), 0)
    t, p = parts.select_cut(keys, jnp.int32(3), tile,
                                 jnp.minimum(TOPK, jnp.asarray(qpos) + 1))
    assert (_selected(np.asarray(keys), np.asarray(t), np.asarray(p))
            == want).all()
    # the decode step's: slots by top_k
    sel, valid = sparse_moe.select_rows(jnp.asarray(scores),
                                        jnp.asarray(qpos), TOPK)
    got = np.zeros_like(want)
    for r in range(len(qpos)):
        got[r, np.asarray(sel)[r][np.asarray(valid)[r]]] = True
    assert (got == want).all()


def test_no_token_is_dropped_when_all_route_to_one_expert(params):
    """Every token's first choice is expert 3 and its second expert 5:
    the layer equals every-expert-on-every-token, and expert 3 got all."""
    blk = dict(params["blocks"][0])
    # the router sees only each token's first value, which is the same
    # for all: every token's logits favour expert 3, then expert 5
    router = np.zeros((64, 8), np.float32)
    router[0, 3], router[0, 5] = 4.0, 2.0
    blk["router"] = jnp.asarray(router)
    g = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32).at[:, 0].set(8.0)
    live = jnp.arange(24) < 21                       # three padding rows
    y, counts, _ = experts.expert_layer(blk, g, live, SPEC, jnp.float32)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref.moe_dense(g, blk, 2))
        sorted_ = np.asarray(ref.moe(g, blk, 2)[0])
    assert list(np.asarray(counts)) == [0, 0, 0, 21, 0, 21, 0, 0]
    assert np.abs(np.asarray(y)[:21] - dense[:21]).max() < TOL
    assert np.abs(sorted_ - dense).max() < TOL       # the reference itself
    assert np.abs(np.asarray(y)[21:]).max() == 0.0


def test_span_counts_equal_the_references_routing_and_selection(params, ids,
                                                                want):
    tracer = Tracer(max_events=4096)
    ex = _executor(params, tracer=tracer, name="llm")
    _serve(ex, ids, 29)         # compiles
    table = ex.cache.allocator.alloc(8)
    pos = 0
    while pos < 29:
        ex.prefill_chunk(ids[pos:min(pos + 8, 29)], pos, table, bucket=8,
                         req="r1")
        pos += 8
    taps = want[1]
    spans = [(label, args) for ph, cat, _, label, _, _, args
             in tracer.events() if ph == "X" and cat == "backend" and args]
    chunks = [a for label, a in spans if label == "invoke"
              and a.get("what") == "llm_prefill_chunk"
              and a.get("req") == "r1"]
    assert [c["pos0"] for c in chunks] == [0, 8, 16, 24]
    for c in chunks:
        e = taps["experts"][:, c["pos0"]:c["pos0"] + c["clen"]]
        hist = np.stack([np.bincount(layer.ravel(), minlength=8)
                         for layer in e])
        assert c["req"] == "r1"
        assert c["experts_touched"] == int((hist > 0).sum())
        assert c["expert_load_max"] == int(hist.max())
    before = ex.stats()
    for t in range(29, 33):
        ex.decode([int(ids[t])], [table], [t])
    steps = [args for ph, cat, _, label, _, _, args in tracer.events()
             if ph == "X" and cat == "backend" and label == "invoke"
             and args.get("what") == "llm_decode"][-4:]
    for t, a in zip(range(29, 33), steps):
        hist = np.stack([np.bincount(layer[t], minlength=8)
                         for layer in taps["experts"]])
        assert a["rows"] == 1 and a["kv_tokens"] == t + 1
        assert a["kv_selected"] == min(TOPK, t + 1) \
            == int(taps["attended"][0, t])
        assert a["experts_touched"] == int((hist > 0).sum())
        assert a["idx_slots"] == 64 and a["kv_slots"] == TOPK
        # one row x 2 lies inside one row tile of 64: a visit a touched
        # expert
        assert a["expert_row_tile"] == 64
        assert a["expert_tile_visits"] == a["experts_touched"]
    after = ex.stats()
    assert after["kv_tokens_selected"] - before["kv_tokens_selected"] == 32
    assert after["kv_tokens_scored"] - before["kv_tokens_scored"] \
        == 30 + 31 + 32 + 33
    assert after["expert_steps_layers"] - before["expert_steps_layers"] == 8
    assert after["expert_tile_visits"] - before["expert_tile_visits"] \
        == after["experts_touched_sum"] - before["experts_touched_sum"] > 0
    assert after["expert_tokens"] - before["expert_tokens"] == 4 * 2 * 2


def test_counts_of_an_unsynced_chunk_come_on_the_span_that_resolves_it(
        params, ids, want):
    tracer = Tracer(max_events=4096)
    ex = _executor(params, tracer=tracer, name="llm")
    _serve(ex, ids, 13)         # compiles
    table = ex.cache.allocator.alloc(8)
    ex.prefill_chunk(ids[:8], 0, table, bucket=8, sync=False, req="r2")
    ex.prefill_chunk(ids[8:13], 8, table, bucket=8, sync=False, req="r2")
    ex.decode([int(ids[13])], [table], [13])     # its sync resolves both
    spans = [(label, args) for ph, cat, _, label, _, _, args
             in tracer.events() if ph == "X" and cat == "backend" and args
             and args.get("req") == "r2"]
    assert [label for label, _ in spans] == ["invoke", "invoke", "resolve",
                                             "resolve"]
    assert all("expert_load_max" not in a for _, a in spans[:2])
    for (_, inv), (_, res) in zip(spans[:2], spans[2:]):
        assert (res["pos0"], res["clen"]) == (inv["pos0"], inv["clen"])
        e = want[1]["experts"][:, res["pos0"]:res["pos0"] + res["clen"]]
        hist = np.stack([np.bincount(layer.ravel(), minlength=8)
                         for layer in e])
        assert res["expert_load_max"] == int(hist.max())
    assert ex.stats()["expert_load_chunks"] >= 2


def test_the_pool_holds_the_indexer_keys_in_the_same_blocks(params):
    ex = _executor(params)
    st = ex.cache.stats()
    # 8 slots of 8 values side by side in one row of 64
    assert ex.cache.idx.shape == (2, 40, 1, 64) and st["pools"] == 3
    assert st["block_bytes"] == 2 * 8 * (2 * 2 * 16 + 8) * 4
    assert ex.resident_bytes() >= ex.cache.resident_bytes() \
        == 40 * st["block_bytes"]
    assert (ex.n_heads, ex.n_kv, ex.head_dim) == (8, 2, 16)


def test_a_dense_bundle_gets_two_pools_and_the_same_tokens():
    from nnstreamer_tpu.models import transformer as T

    dense = T.init_params(seed=1, d_model=32, n_heads=4, n_layers=2,
                          vocab=64)
    prompt = np.asarray([3, 9, 27, 17, 5], np.int32)
    want = np.asarray(T.generate(dense, prompt[None], 6, n_heads=4,
                                 max_len=32))[0][len(prompt):]
    assert len(want) == 6
    for model in (dense, ModelBundle(fn=None, params=dense)):
        eng = LLMEngine(model, n_heads=4, block_size=8, num_blocks=16,
                        max_len=32)
        assert eng.cache.idx is None and len(eng.cache.pools()) == 2
        assert eng.cache.stats()["pools"] == 2
        assert "kv_tokens_selected" not in eng.executor.stats()
        req = eng.submit(prompt, max_new_tokens=6)
        eng.drain()
        assert req.tokens == [int(t) for t in want]


def _refused(params, match, **kw):
    with pytest.raises(BackendError, match=match):
        LLMEngine(ModelBundle(fn=None, params=params, lm=SPEC),
                  dtype=jnp.float32, block_size=8, num_blocks=40,
                  max_len=64, **kw)


REFUSALS = {
    "shards": ({"shards": 2}, "shards=2"),
    "pallas": ({"paged_kernel": "pallas"}, "paged_kernel=pallas"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_typed_refusal_at_construction(params, case):
    kw, match = REFUSALS[case]
    _refused(params, match, **kw)


def test_typed_refusal_of_a_w8a8_store_version(params):
    quant = dict(params, blocks=[dict(b, wqkv_scale=jnp.ones((1,)))
                                 for b in params["blocks"]])
    _refused(quant, "W8A8")


def test_typed_refusal_of_a_long_prompt_without_prefill_chunk(params,
                                                              monkeypatch):
    monkeypatch.setattr(SparseMoESet, "WHOLE_PROMPT_MAX", 16)
    bundle = ModelBundle(fn=None, params=params, lm=SPEC)
    kw = dict(dtype=jnp.float32, block_size=8, num_blocks=40, max_len=64)
    eng = LLMEngine(bundle, **kw)
    with pytest.raises(BackendError, match="set prefill_chunk"):
        eng.submit(np.arange(20, dtype=np.int32), max_new_tokens=2)
    eng.submit(np.arange(16, dtype=np.int32), max_new_tokens=2)
    chunked = LLMEngine(bundle, prefill_chunk=8, **kw)
    chunked.submit(np.arange(20, dtype=np.int32), max_new_tokens=2)


def test_the_engine_serves_the_family_with_chunked_prefill(params, ids,
                                                           want):
    """Through LLMEngine: a long prompt in chunks beside a decoding row;
    greedy tokens are the reference's own choices."""
    eng = LLMEngine(ModelBundle(fn=None, params=params, lm=SPEC),
                    dtype=jnp.float32, block_size=8, num_blocks=40,
                    max_len=64, max_batch=2, prefill_chunk=8)
    short = eng.submit(ids[:5], max_new_tokens=6)
    long_ = eng.submit(ids[:29], max_new_tokens=3)
    eng.drain()
    assert long_.tokens[0] == int(np.argmax(want[0][28]))
    assert short.tokens[0] == int(np.argmax(want[0][4]))
    st = eng.stats()["executor"]
    assert st["chunk_prefills"] >= 5 and st["family"] == "sparse_moe"


# -- the launch-ahead step (llm/engine.py) through this family ---------------

def _engine(params, **kw):
    kw.setdefault("max_batch", 2)
    return LLMEngine(ModelBundle(fn=None, params=params, lm=SPEC),
                     dtype=jnp.float32, block_size=8, num_blocks=40,
                     max_len=64, prefill_chunk=8, **kw)


def _resolved_order(monkeypatch):
    """Every step resolves before it launches, as with a sampled row."""
    monkeypatch.setattr(LLMEngine, "_runs_ahead",
                        lambda self, pending: False)


def test_eos_with_the_next_step_in_flight_in_this_family(params, ids,
                                                         monkeypatch):
    """A row stops on its eos_id with the next launch made: one token
    discarded and counted, the streams those of the resolved order, and
    the expert counts of every launch read, the discarded row's too."""
    def serve(eos):
        eng = _engine(params)
        a = eng.submit(ids[:13], max_new_tokens=8, eos_id=eos)
        b = eng.submit(ids[:5], max_new_tokens=10)
        eng.drain()
        return eng, list(a.tokens), list(b.tokens)

    _, probe, _ = serve(None)
    eos = probe[3]
    stop = probe.index(eos)
    eng, a, b = serve(eos)
    st = eng.stats()
    assert a == probe[:stop + 1] and len(b) == 10
    assert st["lookahead_discarded"] == 1 and st["lookahead_steps"] > 0
    assert st["cache"]["blocks_used"] == 0
    ex = st["executor"]
    assert ex["expert_steps_layers"] == 2 * ex["decode_steps"]
    assert st["tokens_out"] == len(a) + len(b)
    _resolved_order(monkeypatch)
    eng, a_r, b_r = serve(eos)
    assert (a_r, b_r) == (a, b) and eng.lookahead_steps == 0
    # the discarded token was one more row of a launch b needed anyway
    assert eng.stats()["executor"]["decode_steps"] == ex["decode_steps"]


def test_a_sampled_row_in_this_family(params, ids, monkeypatch):
    """A row with temperature > 0 beside a greedy one: its tokens are
    those of its seed served alone, the greedy row's those of the
    resolved order, and the steps after it run ahead again."""
    kw = dict(max_new_tokens=4, temperature=0.7, top_k=4, seed=5)
    alone = _engine(params)
    want = alone.submit(ids[:13], **kw)
    alone.drain()
    assert alone.lookahead_steps == 0
    eng = _engine(params)
    g = eng.submit(ids[:5], max_new_tokens=12)
    s = eng.submit(ids[:13], **kw)
    while s.state != "done":
        eng.step()
    held = eng.lookahead_steps
    eng.drain()
    assert s.tokens == want.tokens and len(s.tokens) == 4
    assert eng.lookahead_steps > held
    assert eng.lookahead_steps < eng.stats()["executor"]["decode_steps"]
    _resolved_order(monkeypatch)
    ref_eng = _engine(params)
    g_r = ref_eng.submit(ids[:5], max_new_tokens=12)
    ref_eng.drain()
    assert g.tokens == g_r.tokens and ref_eng.lookahead_steps == 0
