"""The latent family (llm/latent_moe.py: one compressed row and one roped
key a token in a pool with no head axis and no values, read absorbed or
expanded; a dense layer in front, shared experts beside a share of routed
experts chosen inside groups) against its plain reference in float32,
through the cache, the executor and the engine."""

import ast
import dataclasses
import inspect
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_hybrid                                              # noqa: E402
import tiny_latent_moe as tiny                                  # noqa: E402
import tiny_sparse_moe                                          # noqa: E402
import tiny_window_moe                                          # noqa: E402
from nnstreamer_tpu.backends import pallas_ops, pallas_paged    # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import (                                # noqa: E402
    experts, families, latent_moe, parts)
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.paged_cache import PagedKVCache         # noqa: E402
from nnstreamer_tpu.llm.spec import LMSpec                      # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import latent_moe_lm as ref           # noqa: E402
from perfbench.references import (                              # noqa: E402
    hybrid_lm, sparse_moe_lm, window_moe_lm)
from perfbench.runners import (                                 # noqa: E402
    hybrid_llm, sparse_moe_llm, window_moe_llm)
from perfbench.runners.latent_moe_llm import lm_spec            # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
M = ref.dims(CFG)
SEED = 2**31 + 11
BS, CHUNK = 4, 8
POOL = dict(block_size=BS, num_blocks=80, max_len=64)
TOL = 1e-4          # float32 on the CPU: sums in another order only

# deepseek-ai/DeepSeek-V2 at its published widths
PUBLISHED = LMSpec(
    family="latent_moe", n_heads=128, q_rank=1536, kv_rank=512, nope_dim=128,
    rope_dim=64, v_dim=128, rope_theta=10000.0, yarn_factor=40.0,
    yarn_orig_len=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707, n_experts=160,
    experts_per_tok=6, n_group=8, topk_group=3, route_norm=False,
    route_scale=16.0)


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


# -- whole prompts, chunks, then decode, through the cache, on logits -----------

def _serve(ex, ids, plen, chunk=CHUNK):
    """ids teacher-forced through the executor: the prompt's first `plen`
    in chunks of `chunk` (0: whole), the rest a decode step each. Returns
    the logits after positions plen - 1 .. len(ids) - 1."""
    cache = ex.cache
    blocks, _ = cache.reserve(cache.blocks_for(len(ids)))
    if chunk:
        for at in range(0, plen, chunk):
            n = min(chunk, plen - at)
            lg = ex.prefill_chunk(ids[at:at + n], at, blocks, bucket=chunk)
    else:
        lg = ex.prefill(ids[:plen], blocks)
    out = [np.asarray(lg)]
    for t in range(plen, len(ids)):
        out.append(ex.decode([int(ids[t])], [blocks], [t])[0])
    cache.release(blocks, None)
    return np.stack(out)


# a whole prompt in one bucket, a prompt in chunks whose last is short, one
# that ends on a chunk's edge, a long one; contexts past YaRN's original 16
@pytest.mark.parametrize("plen,total,chunk", [
    (5, 9, 0), (13, 20, 0), (12, 18, CHUNK), (16, 21, CHUNK),
    (33, 45, CHUNK)])
@pytest.mark.parametrize("tile", [8, 1024])
def test_prompts_chunks_and_decode_give_the_references_logits(
        bundle, params, monkeypatch, plen, total, chunk, tile):
    # the tile is a static argument of the chunk program: a small one
    # makes the walk's trip count do the work
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    ids = _prompt(total, seed=plen)
    ex = _executor(bundle)
    got = _serve(ex, ids, plen, chunk)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[plen - 1:]
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert ex.cache.allocator.used == 0


@pytest.mark.parametrize("tile", [8, 1024])
def test_the_two_forms_agree_on_the_same_cache(bundle, params, monkeypatch,
                                               tile):
    """The absorbed and the expanded chunk write the same pools and give
    the same logits, the reference's; the decode step (absorbed) reads
    what either wrote."""
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    ids = _prompt(40, seed=5)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[28:]
    got, pools, said = {}, {}, {}
    for form in (False, True):
        monkeypatch.setattr(latent_moe, "expanded_attend",
                            lambda c, spec, form=form: form)
        ex = _executor(bundle)
        got[form] = _serve(ex, ids, 29)
        pools[form] = [np.asarray(p) for p in ex.cache.pools()]
        said[form] = ex.programs.stats()
    for form in (False, True):
        assert np.abs(got[form] - want).max() < TOL
    assert np.abs(got[True] - got[False]).max() < TOL
    for a, b in zip(pools[False], pools[True]):
        # the first layer's rows bit for bit; the others' follow its
        # attention, whose sums the forms add in another order
        assert np.array_equal(a[0], b[0]) and np.abs(a - b).max() < TOL
        assert np.abs(a[:, 1:]).max() > 0.1
    # only the expanded form puts context through Wkvb again
    assert said[False]["latents_expanded"] == 0
    # a chunk at `at` walks the tiles up to its own last: 4 chunks
    tiles = sum(min(-(-(at + CHUNK) // tile), -(-64 // tile))
                for at in range(0, 29, CHUNK))
    assert said[True]["latents_expanded"] == tiles * tile
    assert said[True]["chunk_tiles_attended"] == tiles
    assert said[True]["chunk_tiles_attended"] \
        == said[False]["chunk_tiles_attended"]


def test_the_expanded_form_through_the_kernel_agrees_with_the_absorbed():
    """The walk at a head's published widths (128 + 64 | 128), the
    expanded form through `pallas_ops.selected_block_update` (interpreted
    here; a head's K filled up to 256 and its V 128 wide) against the
    absorbed form through the plain update, over two tiles of a table."""
    spec = dataclasses.replace(PUBLISHED, n_heads=2, kv_rank=32)
    rng = np.random.default_rng(4)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    c, tile = 128, 128
    tab = jnp.asarray([3, 1, 2, 5], jnp.int32)         # 4 blocks of 64
    qpos = jnp.arange(2 * tile - c, 2 * tile)
    args = (normal(c, 2, 128), normal(c, 2, 64), qpos, tab, (0, 2), 1,
            normal(2, 6, 64, 1, 32), normal(2, 6, 32, 128),
            normal(32, 2, 256) * 0.2)
    kw = dict(tile=tile, spec=spec, dtype=jnp.float32)
    fused = latent_moe.attend_tiles(*args, expanded=True, fused=True, **kw)
    plain = latent_moe.attend_tiles(*args, expanded=True, fused=False, **kw)
    absorbed = latent_moe.attend_tiles(*args, expanded=False, fused=False,
                                       **kw)
    assert fused.shape == (c, 2 * 128)
    assert float(jnp.abs(absorbed).max()) > 0.5
    assert np.abs(np.asarray(fused) - np.asarray(absorbed)).max() < TOL
    assert np.abs(np.asarray(plain) - np.asarray(absorbed)).max() < TOL


@pytest.mark.parametrize("tile", [4, 16])
def test_expanded_chunks_through_the_causal_kernel_say_what_it_did(
        bundle, params, monkeypatch, tile):
    """Every chunk expanded and through `pallas_ops.causal_block_update`
    (interpreted; a program takes 2 queries of the bucket of 8): the
    reference's logits, and the chunk's span says the three kinds of
    (block of queries, tile) pair, which add up to the walk's trip count x
    blocks x layers; a head is a group of one."""
    monkeypatch.setattr(parts, "CTX_TILE", tile)
    monkeypatch.setattr(latent_moe, "expanded_attend", lambda c, spec: True)
    monkeypatch.setattr(parts, "fused_attend", lambda c, tile, hd: True)
    monkeypatch.setattr(pallas_ops, "causal_block_q", lambda c, grp: 2)
    ids = _prompt(40, seed=5)
    tracer = Tracer(max_events=8192)
    ex = _executor(bundle, tracer=tracer, name="llm")
    kw = ex.programs.chunk_kw(0, CHUNK)
    assert kw["fused"] is True and kw["expanded"] is True
    got = _serve(ex, ids, 29)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[28:]
    assert np.abs(got - want).max() < TOL
    chunks = [a for ph, cat, _, label, _, _, a in tracer.events()
              if ph == "X" and cat == "backend" and label == "invoke"
              and a.get("what") == "llm_prefill_chunk"]
    assert [a["pos0"] for a in chunks] == [8, 16, 24]   # the first compiled
    kinds = families.QBLOCK_KINDS
    assert kinds == ("chunk_qblocks_clear", "chunk_qblocks_edge",
                     "chunk_qblocks_skipped")

    def by_hand(pos0):
        said = [0, 0, 0]
        for j in range(min(-(-(pos0 + CHUNK) // tile), 64 // tile)):
            for q0 in range(pos0, pos0 + CHUNK, 2):
                pairs = [s <= q for q in (q0, q0 + 1)
                         for s in range(j * tile, (j + 1) * tile)]
                said[0 if all(pairs) else 2 if not any(pairs) else 1] += 3
        return said                               # three layers

    for a in chunks:
        said = [a[k] for k in kinds]
        assert said == by_hand(a["pos0"])
        assert sum(said) == a["ctx_tiles"] * (CHUNK // 2) * 3
    st = ex.programs.stats()
    for i, k in enumerate(kinds):
        assert st[k] == by_hand(0)[i] + sum(a[k] for a in chunks)
    assert st["chunk_qblocks_clear"] > 0 and st["chunk_qblocks_edge"] > 0
    # a chunk's first blocks lie under its second tile of 4, which starts
    # past them
    assert (st["chunk_qblocks_skipped"] > 0) == (tile == 4)


def test_an_absorbed_or_plain_chunk_counts_no_program(bundle):
    ex = _executor(bundle)
    _serve(ex, _prompt(20, seed=2), 14)
    st = ex.programs.stats()
    assert st["chunk_tiles_attended"] > 0
    assert all(st[k] == 0 for k in families.QBLOCK_KINDS)


def test_the_form_follows_from_the_bucket_alone():
    # 2 H (2 x 512 + 64) absorbed, 2 H (128 + 64 + 128) expanded, 2 x 512
    # x H x 256 a key's expansion: they cross at 170.7 queries a key
    assert not latent_moe.expanded_attend(128, PUBLISHED)
    assert latent_moe.expanded_attend(256, PUBLISHED)
    assert latent_moe.expanded_attend(2048, PUBLISHED)
    cross = 512 * 256 / (2 * 512 + 64 - 320)
    assert 170 < cross < 171
    assert not latent_moe.expanded_attend(170, PUBLISHED)
    assert latent_moe.expanded_attend(171, PUBLISHED)
    # the tiny widths cross at 16: the chunk of 8 is absorbed
    assert not latent_moe.expanded_attend(8, SPEC)
    assert latent_moe.expanded_attend(32, SPEC)


# -- YaRN by hand ---------------------------------------------------------------

def test_yarn_frequencies_and_the_scores_scale_by_hand():
    f = latent_moe.yarn_freqs(PUBLISHED)
    assert f.shape == (32,)

    def corr(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))

    assert math.floor(corr(32)) == 10 and math.ceil(corr(1)) == 23
    for i in range(32):
        e = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        assert f[i] == pytest.approx(e * (1 - ramp) + e / 40 * ramp,
                                     rel=1e-6)
    assert f[10] == pytest.approx(10000.0 ** (-20 / 64), rel=1e-6)
    assert f[23] == pytest.approx(10000.0 ** (-46 / 64) / 40, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26081, abs=1e-5)
    assert latent_moe.score_scale(PUBLISHED) == pytest.approx(
        192 ** -0.5 * m * m)
    # the issue's 0.114723 squares m rounded to 1.26081; unrounded:
    assert latent_moe.score_scale(PUBLISHED) == pytest.approx(0.1147214,
                                                              abs=1e-7)
    assert latent_moe.rope_gain(PUBLISHED) == 1.0
    # the reference reckons them on its own
    assert np.allclose(ref.yarn_freqs(64, 10000.0, (40.0, 4096, 32.0, 1.0,
                                                    0.707, 0.707)), f)
    # plain rope where the spec names no factor
    plain = dataclasses.replace(PUBLISHED, yarn_factor=0.0)
    assert latent_moe.yarn_freqs(plain)[5] == pytest.approx(
        10000.0 ** (-10 / 64))
    assert latent_moe.score_scale(plain) == pytest.approx(192 ** -0.5)


def test_rope_turns_the_pairs_and_keeps_the_dot_product():
    """The program writes (first values | second values), the reference
    turns the pairs in place: the same dot products."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(6, 3, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 19, 33, 60], jnp.int32)
    pq, pk = latent_moe._rope(q, pos, SPEC), latent_moe._rope(k, pos, SPEC)
    rq, rk = ref._rope(q, pos, M), ref._rope(k, pos, M)
    assert np.allclose(np.einsum("nhd,md->nhm", pq, pk),
                       np.einsum("nhd,md->nhm", rq, rk), atol=1e-5)
    f = latent_moe.yarn_freqs(SPEC)
    x1, x2 = float(k[3, 0]), float(k[3, 1])
    a = 19 * float(f[0])
    assert float(rk[3, 0]) == pytest.approx(
        x1 * math.cos(a) - x2 * math.sin(a), abs=1e-5)
    assert float(pk[3, 2]) == pytest.approx(
        x1 * math.sin(a) + x2 * math.cos(a), abs=1e-5)


# -- the router -----------------------------------------------------------------

def _route_by_hand(s, groups, topk_group, k, scale):
    """One token's scores s (E,) -> {expert: weight}, a loop at a time."""
    per = len(s) // groups
    best = [max(s[g * per:(g + 1) * per]) for g in range(groups)]
    stay = sorted(range(groups), key=lambda g: (-best[g], g))[:topk_group]
    open_ = [e for e in range(len(s)) if e // per in stay]
    chosen = sorted(open_, key=lambda e: (-s[e], e))[:k]
    return {e: scale * s[e] for e in chosen}


def test_router_against_a_loop_in_plain_python(params):
    blk = params["blocks"][1]
    u = jnp.asarray(np.random.default_rng(3).normal(size=(40, 64)),
                    jnp.float32)
    p, e = experts.route(blk, u, SPEC, jnp.float32)
    rp, re = ref.route(u, blk["router"], M)
    s = np.asarray(jax.nn.softmax(jnp.matmul(
        u, blk["router"], precision=jax.lax.Precision.HIGHEST), axis=-1))
    groups_seen = set()
    for t in range(40):
        want = _route_by_hand(list(s[t]), 8, 3, 4, 16.0)
        for got_p, got_e in ((p, e), (rp, re)):
            got = dict(zip(np.asarray(got_e[t]).tolist(),
                           np.asarray(got_p[t]).tolist()))
            assert set(got) == set(want)
            for x in want:
                assert got[x] == pytest.approx(want[x], rel=1e-4)
        assert len({x // 2 for x in want}) <= 3         # 3 groups of 8
        groups_seen |= {x // 2 for x in want}
        # not renormalised: 16 x the scores as they are
        assert sum(want.values()) == pytest.approx(
            16 * sum(s[t][x] for x in want), rel=1e-6)
        assert sum(want.values()) < 16.0
    assert len(groups_seen) > 3


def test_router_ties_go_to_the_lower_index():
    """Equal scores everywhere: groups 0-2 stay, and of their experts the
    first four."""
    blk = {"router": jnp.zeros((64, 16), jnp.float32)}
    u = jnp.ones((2, 64), jnp.float32)
    p, e = experts.route(blk, u, SPEC, jnp.float32)
    assert np.asarray(e).tolist() == [[0, 1, 2, 3]] * 2
    assert np.allclose(np.asarray(p), 16.0 / 16)
    rp, re = ref.route(u, blk["router"], M)
    assert np.asarray(re).tolist() == [[0, 1, 2, 3]] * 2
    # one expert far ahead pulls its group in, and the group's other
    # expert comes with it before any expert of a group left out
    w = np.zeros((64, 16), np.float32)
    w[0, 13] = 1.0
    p, e = experts.route({"router": jnp.asarray(w)}, u, SPEC,
                             jnp.float32)
    assert np.asarray(e)[0].tolist() == [13, 0, 1, 2]
    assert _route_by_hand([1.0] * 13 + [2.0] + [1.0] * 2, 8, 3, 4, 1.0) \
        == {13: 2.0, 0: 1.0, 1: 1.0, 2: 1.0}


def _jaxprs(route):
    """The tiny decode steps of the sparse-expert and the window family,
    traced with `route` as the router."""
    experts.route = route
    jax.clear_caches()
    out = []
    for mod, runner, lm in ((tiny_sparse_moe, sparse_moe_llm, sparse_moe_lm),
                            (tiny_window_moe, window_moe_llm, window_moe_lm)):
        cfg = mod.CONFIG
        spec = runner.lm_spec(cfg)
        p = lm.make_params(cfg, SEED, dtype=jnp.float32)
        ex = PagedLLMExecutor(ModelBundle(fn=None, params=p, lm=spec),
                              dtype=jnp.float32, state_slots=4,
                              prefill_chunk=4, block_size=4, num_blocks=48,
                              max_len=64)
        ps = ex.programs
        tab = np.zeros((4, ex.max_blocks), np.int32)
        args = ps.decode_args(p, np.zeros((4,), np.int32), tab,
                              np.zeros((4,), np.int32), 2, ex.cache.pools(),
                              np.zeros((4,), np.int32), tab)
        out.append(str(jax.make_jaxpr(
            lambda *a, ps=ps: ps.program("decode").fn(*a, **ps.kw))(*args)))
    return out


def test_the_other_expert_families_programs_are_as_before_the_groups():
    """Keye's and Trinity's tiny decode steps trace to the same program
    under `_route` as it is and as PR 40 had it: its new branch is keyed
    by the spec alone."""
    def old_route(blk, g, spec, dtype):
        k = spec.experts_per_tok
        logits = jnp.dot(g, blk["router"].astype(dtype),
                         preferred_element_type=jnp.float32)
        if spec.score_fn == "softmax":
            p, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
            return p / jnp.sum(p, axis=-1, keepdims=True), e
        s = jax.nn.sigmoid(logits)
        _, e = jax.lax.top_k(s + blk["router_bias"].astype(jnp.float32), k)
        p = jnp.take_along_axis(s, e, axis=-1)
        return spec.route_scale * p / (
            jnp.sum(p, axis=-1, keepdims=True) + 1e-20), e

    new_route = experts.route
    try:
        new, old = _jaxprs(new_route), _jaxprs(old_route)
    finally:
        experts.route = new_route
        jax.clear_caches()
    assert new == old and len(new[0]) > 1000 and "top_k" in new[0]


def _chunk_jaxprs():
    """The tiny chunk programs of the sparse-expert and the hybrid family
    (Keye's and SALA's), the tile update the fused one."""
    out = []
    for mod, runner, lm in ((tiny_sparse_moe, sparse_moe_llm, sparse_moe_lm),
                            (tiny_hybrid, hybrid_llm, hybrid_lm)):
        cfg = mod.CONFIG
        p = lm.make_params(cfg, SEED, dtype=jnp.float32)
        ex = PagedLLMExecutor(
            ModelBundle(fn=None, params=p, lm=runner.lm_spec(cfg)),
            dtype=jnp.float32, state_slots=4, prefill_chunk=8, block_size=4,
            num_blocks=48, max_len=64)
        ps = ex.programs
        kw = ps.chunk_kw(8, 8)
        assert kw["fused"] is True
        z = np.zeros((8,), np.int32)
        tab = np.zeros((ex.max_blocks,), np.int32)
        args = ps.chunk_args(p, np.zeros((1, 8), np.int32), np.int32(8), z,
                             z, tab, np.int32(7), ex.cache.pools(),
                             np.int32(0), None)
        out.append(str(jax.make_jaxpr(
            lambda *a, ps=ps, kw=kw: ps.program("chunk").fn(*a, **kw))(
                *args)))
    return out


def test_keyes_and_salas_chunk_programs_do_not_reach_the_causal_form(
        monkeypatch):
    """The sparse-expert and the hybrid family's chunks, their tile
    update the fused one, trace to the same program with the causal
    form's three names in `pallas_ops` and without them: they call the
    selected form, whose text this PR left as it was, and nothing of the
    new entry."""
    monkeypatch.setattr(parts, "fused_attend", lambda c, tile, hd: True)
    with_it = _chunk_jaxprs()
    for name in ("causal_block_update", "causal_block_q", "block_reach",
                 "_causal_block_kernel"):
        monkeypatch.delattr(pallas_ops, name)
    without = _chunk_jaxprs()
    assert with_it == without
    for text in with_it:
        assert len(text) > 1000 and "selected_block_update" in text
        assert "causal_block_update" not in text


# -- the share of the experts -----------------------------------------------------

def _uncut():
    """The tiny model with all 16 published experts held."""
    return dict(CFG, n_routed_experts=16,
                expert_share={"published": 16, "first": 0})


def test_the_eight_groups_parts_add_up_to_the_uncut_layer():
    """What each of the eight chips' held experts add, and the shared
    experts and everything outside the expert layer once, is the uncut
    reference's layer: the guide's test of a cut by the chip's share."""
    whole = ref.make_params(_uncut(), SEED, dtype=jnp.float32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, 64)),
                    jnp.float32)
    full, _ = ref.routed_part(u, whole, ref.dims(_uncut()))
    parts = []
    for chip in range(8):
        first = 2 * chip
        share = dict(whole, ewi=whole["ewi"][first:first + 2],
                     ewd=whole["ewd"][first:first + 2])
        parts.append(ref.routed_part(u, share, dict(M, first=first))[0])
        # the program's layer, told the same share
        spec = dataclasses.replace(SPEC, experts_first=first)
        y, counts, away = experts.expert_layer(
            share, u, jnp.ones((24,), bool), spec, jnp.float32)
        assert np.abs(np.asarray(y) - np.asarray(parts[-1])).max() < TOL
        assert int(counts.sum()) + int(away) == 24 * 4
    assert np.abs(np.asarray(sum(parts)) - np.asarray(full)).max() < TOL
    assert float(jnp.abs(full).max()) > 0.1
    # a token's experts lie on at most 3 of the 8 chips
    _, e = ref.route(u, whole["router"], M)
    assert max(len(set(row // 2)) for row in np.asarray(e)) <= 3
    # the whole layer: attention and shared experts once, the parts summed
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 64)), jnp.float32)
    uncut = ref._Static(ref.dims(_uncut()))
    y_full, _ = ref._layer(x, whole, m=uncut, quant=None, q_block=8)
    # a share whose experts are zeros: attention and shared experts alone
    y_none, _ = ref._layer(x, dict(whole, ewi=whole["ewi"][:2] * 0,
                                   ewd=whole["ewd"][:2] * 0),
                           m=uncut, quant=None, q_block=8)
    routed = sum(
        ref._layer(x, dict(whole, ewi=whole["ewi"][2 * c:2 * c + 2],
                           ewd=whole["ewd"][2 * c:2 * c + 2]),
                   m=ref._Static(dict(uncut, first=2 * c)), quant=None,
                   q_block=8)[0] - y_none for c in range(8))
    assert np.abs(np.asarray(y_none + routed) - np.asarray(y_full)).max() \
        < TOL


# -- the pool ---------------------------------------------------------------------

def test_the_pool_holds_a_latent_and_a_roped_key_a_token_and_no_values():
    c = PagedKVCache(num_blocks=6, block_size=64, n_layers=6, n_kv=1,
                     head_dim=512, idx_dim=64, dtype=jnp.bfloat16,
                     values=False)
    assert c.v is None and len(c.pools()) == 2
    k, idx = c.pools()
    assert k.shape == (6, 6, 64, 1, 512)
    assert idx.shape == (6, 6, 32, 128) and c.idx_pack == 2
    # what the arrays hold: 1,152 bytes a token a layer
    assert c.block_bytes == sum(int(p.nbytes) for p in c.pools()) // 6
    assert c.block_bytes == 6 * 64 * 1152
    assert c.resident_bytes() == 6 * c.block_bytes
    assert c.stats()["pools"] == 2
    # the pools go round as they came
    c.set_pools((k + 1, idx + 2))
    assert float(c.k[0, 0, 0, 0, 0]) == 1.0 and float(c.idx[0, 0, 0, 0]) == 2
    assert c.v is None
    # a cache with values answers as it always did
    d = PagedKVCache(num_blocks=4, block_size=8, n_layers=2, n_kv=2,
                     head_dim=16)
    assert len(d.pools()) == 2 and d.v is not None
    assert d.block_bytes == 2 * 8 * 2 * 2 * 16 * 4


def test_the_executor_builds_the_pool_the_family_says(bundle):
    ex = _executor(bundle)
    k, idx = ex.cache.pools()
    assert k.shape == (3, 80, BS, 1, 16) and idx.shape == (3, 80, 1, 16)
    assert ex.cache.v is None
    assert ex.cache.block_bytes == 3 * BS * (16 + 4) * 4
    assert ex.programs.head_dim == 16 and ex.programs.idx_dim == 4


# -- through the engine ----------------------------------------------------------

@pytest.mark.parametrize("chunk_every", [1, 3])
def test_engine_serves_the_references_tokens(bundle, params, chunk_every):
    eng = _engine(bundle, chunk_every=chunk_every)
    reqs = [eng.submit(_prompt(p, seed=p), max_new_tokens=n)
            for p, n in [(5, 3), (12, 6), (20, 8), (33, 5), (3, 4), (18, 9),
                         (29, 12), (4, 20)]]
    eng.drain()
    for r in reqs:
        ids = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        lg = np.asarray(ref.forward_logits(params, CFG, ids))
        lg = lg[len(r.prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() < TOL, r.req_id
    st = eng.stats()
    assert st["lookahead_steps"] > 0 and eng.finished == 8
    assert st["cache"]["blocks_used"] == 0 and st["cache"]["pools"] == 2
    assert st["cache"]["block_bytes"] == 3 * BS * 20 * 4
    ex = st["executor"]
    assert ex["family"] == "latent_moe"
    # 2 of 16 experts held: an eighth of the pairs where routing is even
    share = ex["expert_pairs_held"] / (ex["expert_pairs_held"]
                                       + ex["expert_pairs_away"])
    assert 0.02 < share < 0.4
    assert ex["kv_tokens_attended"] > 0
    assert ex["kv_slots_read"] >= ex["kv_tokens_attended"] // 3
    assert sum(eng.rows[k] for k in eng.rows if k != "total") \
        == eng.rows["total"]


def test_the_reference_counts_the_programs_pairs(bundle, params):
    """The pairs the program says it held and routed away are the
    reference's own routing of the same tokens."""
    ids = _prompt(24, seed=9)
    ex = _executor(bundle)
    _serve(ex, ids, 24)
    taps = {}
    ref.forward_logits(params, CFG, ids, taps=taps)
    e = taps["experts"]                               # (2, 24, 4)
    held = int(((e >= 4) & (e < 6)).sum())
    st = ex.programs.stats()
    assert st["expert_pairs_held"] == held
    assert st["expert_pairs_away"] == e.size - held


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
    for key in ("rows", "kv_tokens", "kv_slots", "kv_pool_itemsize", "attend",
                "experts_touched", "expert_pairs_held", "expert_pairs_away"):
        assert key in decode[-1], key
    assert decode[-1]["attend"] == "plain"          # the CPU's walk
    for key in ("pos0", "clen", "ctx_tiles", "attend", "latents_expanded",
                *families.QBLOCK_KINDS):
        assert key in chunks[-1], key
    assert chunks[-1]["attend"] == "absorbed"       # a bucket of 8
    resolved = [e[6] for e in tracer.events() if e[3] == "resolve"]
    for key in ("req", "pos0", "clen", "expert_load_max",
                "expert_tile_visits", "expert_tile_fill_pct",
                "expert_pairs_held"):
        assert key in resolved[-1], key
    last = decode[-1]
    assert last["expert_pairs_held"] + last["expert_pairs_away"] \
        == last["rows"] * 4 * 2                # 4 a token, 2 expert layers
    # a bucket of at most 4 rows x 4 lies inside one row tile of 64: a
    # visit a touched expert
    assert last["expert_row_tile"] == 64
    assert last["expert_tile_visits"] == last["experts_touched"]
    counters = eng.stats()["executor"]
    assert counters["expert_tile_rows"] \
        == 64 * counters["expert_tile_visits"] > 0
    assert last["kv_slots"] % (BS * latent_moe.walk_plan(
        BS, 2, 16)[0]) == 0


# -- the decode walk as one kernel a row ------------------------------------------

STEP = 8            # slots a step of the kernel here: two blocks of 4

# (bucket, the live rows' positions, blocks taken in order)
WALKS = {
    "a-row-at-position-0": (1, [0], False),
    "a-context-that-ends-on-a-steps-last-slot": (2, [STEP - 1, 3 * STEP - 1],
                                                 False),
    "a-context-one-past-a-steps-last-slot": (2, [STEP, 3 * STEP], False),
    "a-bucket-of-32-with-5-live-rows": (32, [3, 40, 23, 8, 31], False),
    "a-bucket-of-1": (1, [37], False),
    "tables-in-order": (4, [0, 15, 16, 45], True),
}


def _walk_case(dtype, b, pos, in_order, seed=0, rank=16, rope=4, nh=4,
               nblk=80, mb=12, layers=2):
    """Pools of random values, a table a live row (distinct blocks, out of
    order unless `in_order`; the padding rows' the scratch block) and the
    absorbed queries of a bucket of `b`."""
    rng = np.random.default_rng(seed)
    k_pool = jnp.asarray(rng.standard_normal((layers, nblk, BS, 1, rank)),
                         dtype)
    i_pool = jnp.asarray(
        rng.standard_normal((layers, nblk, BS // 2, 2 * rope)), dtype)
    q = jnp.asarray(rng.standard_normal((b, nh, rank + rope)), dtype)
    free = np.arange(1, nblk) if in_order else rng.permutation(
        np.arange(1, nblk))
    tables, pos_a, at = np.zeros((b, mb), np.int32), np.zeros((b,), np.int32), 0
    pos_a[:len(pos)] = pos
    for r, p in enumerate(pos):
        n = p // BS + 1
        tables[r, :n], at = free[at:at + n], at + n
    return q, k_pool, i_pool, jnp.asarray(tables), jnp.asarray(pos_a)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 6e-3)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WALKS))
def test_the_fused_walks_kernel_agrees_with_the_plain_walk(case, dtype, tol):
    """`pallas_paged.latent_decode_attn`, interpreted, against
    `attend_latent` on the same pools and tables: float32 to rounding
    (only the order of a row's sums differs), bfloat16 within the
    probabilities' rounding to it (the plain walk rounds them against a
    chunk's own maximum, the kernel against the running one)."""
    b, pos, in_order = WALKS[case]
    q, k_pool, i_pool, tables, pos_a = _walk_case(dtype, b, pos, in_order,
                                                  seed=len(case))
    n, li, scale = len(pos), 1, 0.3
    got = pallas_paged.latent_decode_attn(
        q, k_pool, i_pool, jnp.int32(li), tables, pos_a, jnp.int32(n),
        scale=scale, step=STEP, interpret=True)
    nb_c, n_chunks, t = latent_moe.walk_plan(BS, b, tables.shape[1])
    items = parts.live_items(tables, pos_a, BS, nb_c, n_chunks, t)
    want = latent_moe.attend_latent(q, k_pool, i_pool, li, items, t, scale)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert np.abs(np.asarray(got)[:n] - np.asarray(want)[:n]).max() < tol
    # a padding row copies nothing and writes zeros
    assert not np.asarray(got)[n:].any()


def test_the_kernel_reads_the_layer_and_the_blocks_it_is_told():
    """Another layer's rows, or a block the table does not name, change
    nothing; the row's own last block does."""
    q, k_pool, i_pool, tables, pos_a = _walk_case(jnp.float32, 2, [9, 21],
                                                  False)

    def run(k, i):
        return np.asarray(pallas_paged.latent_decode_attn(
            q, k, i, jnp.int32(1), tables, pos_a, jnp.int32(2), scale=0.3,
            step=STEP, interpret=True))

    want = run(k_pool, i_pool)
    named = set(np.asarray(tables)[0, :3]) | set(np.asarray(tables)[1, :6])
    other = next(b for b in range(1, 80) if b not in named)
    assert (run(k_pool.at[0].set(7.0).at[1, other].set(7.0),
                i_pool.at[0].set(7.0).at[1, other].set(7.0)) == want).all()
    last = int(np.asarray(tables)[1, 5])
    moved = run(k_pool.at[1, last, 1].set(7.0), i_pool)
    assert (moved[0] == want[0]).all() and (moved[1] != want[1]).any()
    # a slot past the row's position is not attended: 21 is slot 1 of `last`
    assert (run(k_pool.at[1, last, 2:].set(7.0),
                i_pool.at[1, last, 1:].set(7.0)) == want).all()
    with pytest.raises(ValueError, match="two tokens a row"):
        pallas_paged.latent_decode_attn(
            q, k_pool, i_pool.reshape(2, 80, 1, 16), jnp.int32(1), tables,
            pos_a, jnp.int32(2), scale=0.3, step=STEP, interpret=True)


def _pairs_cfg():
    """The tiny configuration with a roped key of 64: two a packed row of
    128, the layout the kernel reads."""
    return dict(CFG, qk_rope_head_dim=64)


@pytest.fixture
def fused_walk(monkeypatch):
    """The rule forced (the backend here is the CPU, where the kernel is
    interpreted) and a step of two blocks."""
    monkeypatch.setattr(latent_moe, "fused_decode", lambda *a: True)
    monkeypatch.setattr(latent_moe, "DECODE_STEP", STEP)


def test_a_decode_steps_logits_through_both_walks(monkeypatch):
    cfg = _pairs_cfg()
    spec = lm_spec(cfg)
    params = ref.make_params(cfg, SEED, dtype=jnp.float32)
    ex = PagedLLMExecutor(ModelBundle(fn=None, params=params, lm=spec),
                          dtype=jnp.float32, state_slots=4,
                          prefill_chunk=CHUNK, **POOL)
    assert ex.cache.pools()[1].shape[2:] == (BS // 2, 128)
    cache, tables, pos = ex.cache, np.zeros((4, 16), np.int32), [21, 9, 16]
    for r, p in enumerate(pos):
        blocks, _ = cache.reserve(cache.blocks_for(p + 1))
        ids = _prompt(p, seed=r)
        for at in range(0, p, CHUNK):
            ex.prefill_chunk(ids[at:at + CHUNK], at, blocks, bucket=CHUNK)
        tables[r, :len(blocks)] = blocks
    args = (jax.tree.map(jnp.asarray, params), jnp.asarray([5, 6, 7, 0]),
            jnp.asarray(tables), jnp.asarray(pos + [0], jnp.int32),
            jnp.int32(3), *cache.pools())
    kw = dict(spec=spec, dtype=jnp.float32)
    want = latent_moe.latent_moe_decode_step(*args, **kw)
    monkeypatch.setattr(latent_moe, "fused_decode", lambda *a: True)
    monkeypatch.setattr(latent_moe, "DECODE_STEP", STEP)
    calls = []
    monkeypatch.setattr(
        pallas_paged, "latent_decode_attn",
        lambda *a, _f=pallas_paged.latent_decode_attn, **k: (
            calls.append(k["step"]), _f(*a, **k))[1])
    got = latent_moe.latent_moe_decode_step(*args, **kw)
    assert calls == [STEP] * 2          # a trace a kind of layer
    assert np.abs(np.asarray(got[0])[:3] - np.asarray(want[0])[:3]).max() \
        < TOL
    assert (np.asarray(got[0])[:3].argmax(-1)
            == np.asarray(want[0])[:3].argmax(-1)).all()
    # the expert layers' counts are the same, both pools' writes the
    # first layer's to the bit and the later ones' to rounding (but the
    # padding row's, which go to the scratch block)
    assert (np.asarray(got[1]) == np.asarray(want[1])).all()
    for g, w in zip(got[2:], want[2:]):
        assert (np.asarray(g[0]) == np.asarray(w[0])).all()
        assert np.abs(np.asarray(g[:, 1:]) - np.asarray(w[:, 1:])).max() \
            < TOL


def test_the_engine_serves_the_same_tokens_through_the_fused_walk(
        fused_walk):
    cfg = _pairs_cfg()
    params = ref.make_params(cfg, SEED, dtype=jnp.float32)
    tracer = Tracer()
    eng = LLMEngine(ModelBundle(fn=None, params=params, lm=lm_spec(cfg)),
                    dtype=jnp.float32, max_batch=4, prefill_chunk=CHUNK,
                    tracer=tracer, **POOL)
    prompts = [_prompt(p, seed=i) for i, p in enumerate((20, 33, 9))]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain()
    for p, r in zip(prompts, reqs):
        ids = np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(ref.forward_logits(params, cfg, ids))[len(p) - 1:]
        assert list(want.argmax(-1)) == list(r.tokens)
    st = eng.executor.stats()
    assert st["decode_steps_fused"] == st["decode_steps"] > 0
    assert st["decode_steps_plain"] == 0
    decode = [e[6] for e in tracer.events() if e[3] == "invoke"
              and e[6].get("what") == "llm_decode"]
    assert decode and all(d["attend"] == "fused" for d in decode)
    assert all(d["kv_slots"] % STEP == 0 for d in decode)


def test_the_rule_reads_the_backend_and_the_pools_widths(monkeypatch):
    bf = jnp.bfloat16
    assert not latent_moe.fused_decode(64, PUBLISHED, bf)     # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert latent_moe.fused_decode(64, PUBLISHED, bf)
    assert latent_moe.fused_decode(64, PUBLISHED, jnp.float32)
    assert latent_moe.fused_decode(32, PUBLISHED, bf)
    assert not latent_moe.fused_decode(64, PUBLISHED, jnp.float16)
    # packed rows of a block that fill no 16-bit tile; a step no whole blocks
    assert not latent_moe.fused_decode(16, PUBLISHED, bf)
    assert not latent_moe.fused_decode(96, PUBLISHED, bf)
    for narrow in (dict(kv_rank=192), dict(rope_dim=32), dict(rope_dim=128)):
        assert not latent_moe.fused_decode(
            64, dataclasses.replace(PUBLISHED, **narrow), bf)
    assert not latent_moe.fused_decode(BS, SPEC, jnp.float32)   # the tiny one


def test_each_walk_counts_the_slots_it_moves(bundle, monkeypatch):
    """`fused_slots`: each live row's context in whole steps, nothing for
    a padding row, which is what the kernel's trip counts copy (a step is
    `DECODE_STEP` slots: `n_j` of `_latent_decode_kernel`); `walk_slots`
    whole iterations of the work list. `note_decode` picks by the rule."""
    pos = np.asarray([0, 1023, 1024, 7000, 0, 0, 0, 0])
    assert latent_moe.fused_slots(pos, 4) == (1 + 1 + 2 + 7) * 1024
    assert latent_moe.fused_slots(pos, 1) == 1024
    ps = _executor(bundle).programs
    nb_c, _, t = latent_moe.walk_plan(BS, 4, 16)
    plain = parts.walk_slots([9, 20, 0, 0], BS, nb_c, t)
    assert plain == 256             # one iteration of 16 chunks of 16 slots
    said = ps.note_decode(np.asarray([9, 20, 0, 0]), 2)
    assert said == {"kv_tokens": 31, "attend": "plain", "kv_slots": plain}
    monkeypatch.setattr(latent_moe, "fused_decode", lambda *a: True)
    monkeypatch.setattr(latent_moe, "DECODE_STEP", STEP)
    said = ps.note_decode(np.asarray([9, 20, 0, 0]), 2)
    assert said == {"kv_tokens": 31, "attend": "fused",
                    "kv_slots": (2 + 3) * STEP}
    st = ps.stats()
    assert (st["decode_steps_plain"], st["decode_steps_fused"]) == (1, 1)
    assert st["kv_slots_read"] == (2 + 3) * STEP + plain


# -- what the family refuses, and the module's classes ------------------------------

def test_refusals(bundle, params):
    with pytest.raises(BackendError, match="paged_kernel=pallas"):
        _executor(bundle, paged_kernel="pallas")
    with pytest.raises(BackendError, match="shards=2.*head axis"):
        LLMEngine(bundle, dtype=jnp.float32, shards=2, **POOL)
    blocks = [dict(params["blocks"][0], wqa_scale=jnp.ones((1,)))] \
        + params["blocks"][1:]
    with pytest.raises(BackendError, match="W8A8"):
        _executor(ModelBundle(fn=None, params=dict(params, blocks=blocks),
                              lm=SPEC))
    with pytest.raises(BackendError, match="at least one layer"):
        _executor(ModelBundle(fn=None, params=params,
                              lm=dataclasses.replace(SPEC, dense_layers=3)))
    with pytest.raises(BackendError, match="roped width even"):
        _executor(ModelBundle(fn=None, params=params,
                              lm=dataclasses.replace(SPEC, rope_dim=3)))
    with pytest.raises(BackendError, match="groups have to be equal"):
        _executor(ModelBundle(fn=None, params=params,
                              lm=dataclasses.replace(SPEC, topk_group=1)))
    # a whole prompt past one chunk's reach needs chunked prefill
    eng = LLMEngine(bundle, dtype=jnp.float32, block_size=4, num_blocks=2000,
                    max_len=6000)
    with pytest.raises(BackendError, match="needs chunked prefill"):
        eng.submit(_prompt(5000), max_new_tokens=4)


def test_each_program_sets_class_is_defined_once():
    """ROADMAP C14: a second `class ChunkOnlySet` shadowed the first."""
    tree = ast.parse(inspect.getsource(families))
    names = [n.name for n in tree.body
             if isinstance(n, (ast.ClassDef, ast.FunctionDef))]
    assert len(names) == len(set(names))
    sets = [n for n in names if n.endswith("Set")]
    assert sets == ["DenseSet", "ChunkOnlySet", "SparseMoESet", "HybridSet",
                    "HeldExpertsSet", "WindowMoESet", "LatentMoESet",
                    "DeltaMoESet"]
    assert set(families.FAMILIES.values()) == {
        getattr(families, n) for n in sets} - {families.ChunkOnlySet,
                                               families.HeldExpertsSet}
    methods = [m.name for c in tree.body if isinstance(c, ast.ClassDef)
               for m in c.body if isinstance(m, ast.FunctionDef)
               and c.name == "ChunkOnlySet"]
    assert "_note_experts" not in methods
    assert len(methods) == len(set(methods))
