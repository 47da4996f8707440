"""Paged Pallas attention kernels vs the XLA paged reference.

Tier-1's half of the ISSUE-16 acceptance gate: the Pallas paged decode
and prefill kernels (backends/pallas_paged.py) run here in interpret
mode and must match `llm/paged_model.py`'s XLA reference to <= 1e-5 on
logits across the block-table shapes serving actually produces —
non-contiguous tables (holes), staggered per-row depths, pow2-padded
batch rows writing to the scratch block, and multi-chunk prefill over
previously written pool blocks. The compiled run of the same kernels
is `chip_smoke.py`, on the chip.
"""

import os
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

from nnstreamer_tpu.backends.pallas_paged import (  # noqa: E402
    paged_flash_decode_step, paged_flash_prefill_chunk)
from nnstreamer_tpu.llm.engine import LLMEngine  # noqa: E402
from nnstreamer_tpu.llm.paged_model import (  # noqa: E402
    paged_decode_step, paged_prefill, paged_prefill_chunk)
from nnstreamer_tpu.models.transformer import init_params  # noqa: E402

TOL = 1e-5
L, NB, BS, NKV, HD, MB = 2, 16, 8, 2, 16, 4     # pool geometry


@pytest.fixture(scope="module")
def params():
    return init_params(vocab=61, d_model=64, n_layers=L, n_heads=4,
                       n_kv_heads=NKV, seed=3)


def _pools():
    z = jnp.zeros((L, NB, BS, NKV, HD), jnp.float32)
    return z, z


def _targets(n, blocks, s_b, pos0=0):
    """Per-position (block, offset) scatter targets; padding → scratch."""
    bi = np.zeros(s_b, np.int32)
    bo = ((pos0 + np.arange(s_b)) % BS).astype(np.int32)
    for j in range(n):
        bi[j] = blocks[(pos0 + j) // BS]
    return jnp.asarray(bi), jnp.asarray(bo)


def _table(blocks):
    t = np.zeros(MB, np.int32)
    t[:len(blocks)] = blocks
    return jnp.asarray(t)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 60, size=n).astype(np.int32)


def _prefill_ref(params, prompt, blocks, kp, vp):
    n = len(prompt)
    s_b = max(8, 1 << (n - 1).bit_length())
    ids = jnp.asarray(np.pad(prompt, (0, s_b - n))[None, :], jnp.int32)
    bi, bo = _targets(n, blocks, s_b)
    return paged_prefill(params, ids, bi, bo, kp, vp, n - 1)


# -- decode parity -----------------------------------------------------------

def test_decode_parity_holey_staggered_padded(params):
    """The full serving batch shape at once: two live rows at different
    depths, non-contiguous (hole-y) block tables, and pow2 padding rows
    whose table is all scratch."""
    kp, vp = _pools()
    # seq0: 12 tokens over blocks [3, 9] (hole); seq1: 5 over [7]
    _, kp, vp = _prefill_ref(params, _prompt(12, 1), [3, 9], kp, vp)
    _, kp, vp = _prefill_ref(params, _prompt(5, 2), [7], kp, vp)
    tabs = np.zeros((4, MB), np.int32)
    tabs[0, :2] = [3, 9]
    tabs[1, 0] = 7
    tabs = jnp.asarray(tabs)
    cur = jnp.asarray([17, 23, 0, 0], jnp.int32)
    pos = jnp.asarray([12, 5, 0, 0], jnp.int32)
    ref, kr, vr = paged_decode_step(params, cur, tabs, pos, kp, vp)
    fl, kf, vf = paged_flash_decode_step(params, cur, tabs, pos, kp, vp)
    assert float(jnp.max(jnp.abs(ref[:2] - fl[:2]))) <= TOL
    # the write-through halves are identical (live blocks only; the
    # scratch block absorbs different padding garbage by design)
    assert float(jnp.max(jnp.abs(kr[:, 1:] - kf[:, 1:]))) <= TOL
    assert float(jnp.max(jnp.abs(vr[:, 1:] - vf[:, 1:]))) <= TOL
    # and a second, deeper step over the updated pools still agrees
    # (seq0 crosses into its second block's tail)
    cur2 = jnp.asarray([9, 11, 0, 0], jnp.int32)
    pos2 = pos + jnp.asarray([1, 1, 0, 0], jnp.int32)
    ref2 = paged_decode_step(params, cur2, tabs, pos2, kr, vr)[0]
    fl2 = paged_flash_decode_step(params, cur2, tabs, pos2, kf, vf)[0]
    assert float(jnp.max(jnp.abs(ref2[:2] - fl2[:2]))) <= TOL


def test_decode_parity_row_at_block_boundary(params):
    """pos exactly at a block edge: the write lands in a fresh block
    while attention spans the full previous one — the off-by-one spot
    for the inclusive <= pos mask."""
    kp, vp = _pools()
    _, kp, vp = _prefill_ref(params, _prompt(BS, 4), [5], kp, vp)
    tabs = jnp.asarray(np.array([[5, 11, 0, 0]], np.int32))
    cur = jnp.asarray([7], jnp.int32)
    pos = jnp.asarray([BS], jnp.int32)          # first slot of block 11
    ref = paged_decode_step(params, cur, tabs, pos, kp, vp)[0]
    fl = paged_flash_decode_step(params, cur, tabs, pos, kp, vp)[0]
    assert float(jnp.max(jnp.abs(ref - fl))) <= TOL


# -- the live-block walk vs the dense gather it replaced ----------------------

WL, WNB, WBS, WMB = 2, 40, 8, 8            # walk geometry: max_len 64
WBLOCK_BYTES = WBS * NKV * HD * 4


def _dense_decode_step(params, cur, tables, pos, k_pool, v_pool, *,
                       n_heads=4):
    """The gather `paged_decode_step` had before it walked live blocks:
    every slot of every table behind a -1e30 mask, float32."""
    import jax

    from nnstreamer_tpu.llm.parts import mlp_paged, proj, rope_rows
    from nnstreamer_tpu.models.transformer import expand_kv, rmsnorm

    b, f32 = cur.shape[0], jnp.float32
    bs, n_kv, hd = k_pool.shape[2:]
    kv_len = tables.shape[1] * bs
    wb, wo = tables[jnp.arange(b), pos // bs], pos % bs
    x = params["embed"][cur][:, None, :]
    mask = jnp.arange(kv_len)[None, None, None, :] \
        <= pos[:, None, None, None]
    for li, blk in enumerate(params["blocks"]):
        qkv = proj(blk, "wqkv", rmsnorm(x, blk["ln1"]), f32)
        d = x.shape[-1]
        kvd = n_kv * hd
        q = rope_rows(qkv[..., :d].reshape(b, 1, n_heads, hd), pos)
        k = rope_rows(qkv[..., d:d + kvd].reshape(b, 1, n_kv, hd), pos)
        v = qkv[..., d + kvd:].reshape(b, 1, n_kv, hd)
        k_pool = k_pool.at[li, wb, wo].set(k[:, 0])
        v_pool = v_pool.at[li, wb, wo].set(v[:, 0])
        kc = expand_kv(k_pool[li][tables].reshape(b, kv_len, n_kv, hd),
                        n_heads)
        vc = expand_kv(v_pool[li][tables].reshape(b, kv_len, n_kv, hd),
                        n_heads)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc) * hd ** -0.5
        pattn = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vc)
        x = x + proj(blk, "wo", attn.reshape(b, 1, -1), f32)
        x = x + mlp_paged(blk, rmsnorm(x, blk["ln2"]), f32)
    x = rmsnorm(x, params["ln_f"])
    return proj(params, "head", x[:, 0], f32), k_pool, v_pool


def _walk_consts(monkeypatch, chunk_blocks, items):
    """Steer the walk's constants from the test: C = chunk_blocks
    blocks a chunk, T = items an iteration (None: as shipped, where a
    table of this size is one chunk)."""
    from nnstreamer_tpu.llm import parts

    if chunk_blocks is not None:
        monkeypatch.setattr(parts, "CHUNK_BYTES",
                            chunk_blocks * WBLOCK_BYTES)
        monkeypatch.setattr(parts, "ITER_BYTES",
                            items * chunk_blocks * WBLOCK_BYTES)


def _walk_batch(pos, live, seed):
    """Pools full of stale garbage, hole-y tables of distinct blocks
    for the `live` first rows, all-scratch tables for the padding rows
    behind them."""
    rng = np.random.default_rng(seed)
    shape = (WL, WNB, WBS, NKV, HD)
    kp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    free = list(rng.permutation(np.arange(1, WNB)))
    tabs = np.zeros((len(pos), WMB), np.int32)
    for r in range(live):
        for j in range(pos[r] // WBS + 1):
            tabs[r, j] = free.pop()
    cur = jnp.asarray(rng.integers(1, 60, len(pos)), jnp.int32)
    return cur, jnp.asarray(tabs), jnp.asarray(pos, jnp.int32), kp, vp


# (pos of the bucket's rows, live rows, blocks a chunk, items an iteration)
WALK_CASES = {
    # C = 16 slots: rows 3, 1, 2 and 4 chunks deep, 10 items in 4
    # iterations of 3, the last one a third full
    "staggered_multichunk": ([37, 5, 20, 50], 4, 2, 3),
    # a chunk's last slot, the next one's first, twice
    "chunk_edges": ([15, 16, 31, 32], 4, 2, 4),
    # 5 items against T = 4: a second iteration with one item
    "last_iteration_part_empty": ([40, 17, 3, 7], 4, 2, 4),
    # one item an iteration: every merge crosses iterations
    "one_item_an_iteration": ([33, 9, 63, 24], 4, 2, 1),
    # more items an iteration than the bucket has: one row's chunks
    # merge inside an iteration
    "row_merges_inside_iteration": ([63, 47], 2, 1, 16),
    # two padding rows share the scratch block with each other
    "padding_rows_share_scratch": ([29, 18, 0, 0], 2, 2, 3),
    "bucket_of_one": ([45], 1, 2, 2),
    "bucket_of_one_first_slot": ([0], 1, 1, 2),
    # the constants as shipped: the whole table is one chunk
    "shipped_constants": ([37, 5, 20, 63], 4, None, None),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_decode_walk_matches_dense_gather(params, monkeypatch, case):
    """The walk over live blocks gives the dense gather's logits to
    1e-5 and its pool writes: every slot the step did not write keeps
    its bits, and the written ones (which below layer 0 follow a
    reassociated attention sum, and in every layer a jitted projection
    against the reference's eager one) agree to 1e-5."""
    pos, live, chunk_blocks, items = WALK_CASES[case]
    _walk_consts(monkeypatch, chunk_blocks, items)
    cur, tabs, pos_a, kp, vp = _walk_batch(pos, live, seed=len(case))
    ref, kr, vr = _dense_decode_step(params, cur, tabs, pos_a, kp, vp)
    out, kw, vw = paged_decode_step(params, cur, tabs, pos_a, kp, vp)
    assert float(jnp.max(jnp.abs(ref[:live] - out[:live]))) <= TOL
    written = np.zeros((WNB, WBS), bool)
    written[np.asarray(tabs)[np.arange(len(pos)), np.asarray(pos) // WBS],
            np.asarray(pos) % WBS] = True
    for new, old, dense in ((kw, kp, kr), (vw, vp, vr)):
        new, old, dense = map(np.asarray, (new, old, dense))
        assert np.array_equal(new[:, ~written], old[:, ~written])
        assert np.max(np.abs(new - dense)) <= TOL


@pytest.mark.parametrize("chunk_blocks,items", [(2, 3), (None, None)])
def test_decode_walk_reads_nothing_past_pos(params, monkeypatch,
                                            chunk_blocks, items):
    """A huge finite value planted in the live blocks' slots past `pos`
    and all over the scratch block changes no bit of the live rows'
    logits: masked slots weigh exactly 0.0."""
    _walk_consts(monkeypatch, chunk_blocks, items)
    pos, live = [37, 5, 20, 0], 3
    cur, tabs, pos_a, kp, vp = _walk_batch(pos, live, seed=9)
    clean = paged_decode_step(params, cur, tabs, pos_a, kp, vp)[0]
    dirty = np.zeros((WNB, WBS), bool)
    dirty[0] = True                              # the scratch block
    for r in range(live):
        for j in range(pos[r] // WBS + 1):
            lo = max(0, pos[r] + 1 - j * WBS)
            dirty[int(tabs[r, j]), lo:] = True
    kd = jnp.where(jnp.asarray(dirty)[None, :, :, None, None], 1e30, kp)
    vd = jnp.where(jnp.asarray(dirty)[None, :, :, None, None], -1e30, vp)
    out = paged_decode_step(params, cur, tabs, pos_a, kd, vd)[0]
    assert np.array_equal(np.asarray(out[:live]), np.asarray(clean[:live]))


def test_launch_ahead_over_chunk_boundary_equals_single_synced_steps(
        params, monkeypatch):
    """Eight launches made ahead of their reads, whose positions cross a
    chunk's edge (C = 16: from slot 13 to slot 20, from 29 to 36), serve
    the tokens of as many single synced steps and count the same
    context."""
    _walk_consts(monkeypatch, 2, 3)
    prompts = [_prompt(13, 40), _prompt(29, 41), _prompt(6, 42)]
    pos = [len(p) for p in prompts]
    served = []
    for ahead in (True, False):
        with monkeypatch.context() as mp:
            if not ahead:
                mp.setattr(LLMEngine, "_runs_ahead",
                           lambda self, pending: False)
            eng = LLMEngine(dict(params), n_heads=4, block_size=WBS,
                            num_blocks=WNB, max_len=WBS * WMB, max_batch=4)
            reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
            eng.drain()
        served.append([list(r.tokens) for r in reqs])
        st = eng.stats()
        assert (st["lookahead_steps"] > 0) == ahead
        assert st["executor"]["decode_steps"] == 8
        assert st["executor"]["kv_tokens_attended"] == sum(
            sum(pos) + len(pos) * (s + 1) for s in range(8))
    assert served[0] == served[1] and [len(t) for t in served[0]] == [9] * 3


def test_decode_bucket_compiles_once_over_all_contexts(params):
    """One decode bucket is one program whatever the rows' depths: the
    walk's extent is a value read from `pos`, not a shape, so contexts
    from 1 to max_len add no compile (what holds `compiles_in_window`
    at 0 behind the benchmark's short warm-up)."""
    from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor

    ex = PagedLLMExecutor(dict(params), n_heads=4, block_size=WBS,
                          num_blocks=WNB, max_len=WBS * WMB)
    tables = [ex.cache.allocator.alloc(WMB) for _ in range(3)]
    ex.decode([5, 6, 7], tables, [0, 0, 0])
    first = ex.compile_count
    assert first == 1
    traces = ex._jits[(ex._ns(), "decode", 4, "xla")]._cache_size()
    for p in range(0, WBS * WMB, 3):
        ex.decode([5, 6, 7], tables,
                  [p, (p * 7) % (WBS * WMB), WBS * WMB - 1 - p])
    assert ex.compile_count == first
    assert ex._jits[(ex._ns(), "decode", 4, "xla")]._cache_size() == traces
    st = ex.stats()
    assert 0 < st["kv_tokens_attended"] <= st["kv_slots_read"]


# -- prefill / chunk parity --------------------------------------------------

def test_chunk_matches_full_prefill_reference(params):
    """One chunk covering the whole prompt == the apply_seq_kv prefill
    (logits AND pool contents) — the bridge that lets the chunk family
    replace whole-prompt prefill for pallas/quantized stores."""
    prompt = _prompt(12, 5)
    n, s_b = 12, 16
    ids = jnp.asarray(np.pad(prompt, (0, s_b - n))[None, :], jnp.int32)
    bi, bo = _targets(n, [3, 9], s_b)
    kp, vp = _pools()
    ref, kr, vr = paged_prefill(params, ids, bi, bo, kp, vp, n - 1)
    kp, vp = _pools()
    chk, kc, vc = paged_prefill_chunk(
        params, ids, jnp.int32(0), bi, bo, _table([3, 9]), kp, vp, n - 1)
    assert float(jnp.max(jnp.abs(ref - chk))) <= TOL
    assert float(jnp.max(jnp.abs(kr[:, 1:] - kc[:, 1:]))) <= TOL
    kp, vp = _pools()
    fl, kf, vf = paged_flash_prefill_chunk(
        params, ids, jnp.int32(0), bi, bo, _table([3, 9]), kp, vp, n - 1)
    assert float(jnp.max(jnp.abs(ref - fl))) <= TOL
    assert float(jnp.max(jnp.abs(kr[:, 1:] - kf[:, 1:]))) <= TOL


@pytest.mark.parametrize("flavor", ["xla", "pallas"])
def test_chunked_equals_unchunked(params, flavor):
    """Three 8-token chunks == one 24-token prefill: later chunks
    attend earlier chunks' pool KV through the table, and the causal
    mask is positional, not chunk-local."""
    fn = paged_prefill_chunk if flavor == "xla" \
        else paged_flash_prefill_chunk
    prompt = _prompt(24, 6)
    blocks = [2, 6, 13]                         # holes on purpose
    tab = _table(blocks)
    kp, vp = _pools()
    ref, _, _ = _prefill_ref(params, prompt, blocks, kp, vp)
    kp, vp = _pools()
    out = None
    for c0 in range(0, 24, 8):
        seg = prompt[c0:c0 + 8]
        ids = jnp.asarray(seg[None, :], jnp.int32)
        bi, bo = _targets(len(seg), blocks, 8, pos0=c0)
        out, kp, vp = fn(params, ids, jnp.int32(c0), bi, bo, tab,
                         kp, vp, len(seg) - 1)
    assert float(jnp.max(jnp.abs(ref - out))) <= TOL


def test_chunk_padded_tail_hits_scratch_only(params):
    """A short final chunk padded to its bucket must leave every live
    block untouched beyond the real tokens — padding rows write to the
    scratch block only."""
    prompt = _prompt(3, 7)
    ids = jnp.asarray(np.pad(prompt, (0, 5))[None, :], jnp.int32)
    bi, bo = _targets(3, [4], 8)
    kp, vp = _pools()
    _, kp, vp = paged_flash_prefill_chunk(
        params, ids, jnp.int32(0), bi, bo, _table([4]), kp, vp, 2)
    # block 4 slots beyond position 2 stay zero
    assert float(jnp.max(jnp.abs(kp[:, 4, 3:]))) == 0.0
    # every other non-scratch block is untouched
    live = np.ones(NB, bool)
    live[[0, 4]] = False
    assert float(jnp.max(jnp.abs(kp[:, live]))) == 0.0


# -- quantized (W8A8) cross-kernel parity ------------------------------------

def test_quantized_chunk_and_decode_parity(params):
    from nnstreamer_tpu.models.quant import quantize_transformer

    qp = quantize_transformer(params)
    prompt = _prompt(10, 8)
    ids = jnp.asarray(np.pad(prompt, (0, 6))[None, :], jnp.int32)
    bi, bo = _targets(10, [3, 8], 16)
    tab = _table([3, 8])
    kp, vp = _pools()
    ref, kr, vr = paged_prefill_chunk(
        qp, ids, jnp.int32(0), bi, bo, tab, kp, vp, 9)
    kp, vp = _pools()
    fl, kf, vf = paged_flash_prefill_chunk(
        qp, ids, jnp.int32(0), bi, bo, tab, kp, vp, 9)
    assert float(jnp.max(jnp.abs(ref - fl))) <= TOL
    tabs = jnp.asarray(np.array([[3, 8, 0, 0]], np.int32))
    cur = jnp.asarray([21], jnp.int32)
    pos = jnp.asarray([10], jnp.int32)
    refd = paged_decode_step(qp, cur, tabs, pos, kr, vr)[0]
    fld = paged_flash_decode_step(qp, cur, tabs, pos, kf, vf)[0]
    assert float(jnp.max(jnp.abs(refd - fld))) <= TOL


# -- engine-level: kernel knob, fallback, chunked serving --------------------

def _run_engine(params, prompts, **kw):
    eng = LLMEngine(dict(params), n_heads=4, block_size=8,
                    num_blocks=64, max_batch=4, max_len=128, **kw)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain()
    return [tuple(r.tokens) for r in reqs], eng


def test_engine_pallas_equals_xla_tokens(params):
    prompts = [_prompt(9, 11), _prompt(21, 12), _prompt(4, 13)]
    base, _ = _run_engine(params, prompts)
    pal, eng = _run_engine(params, prompts, paged_kernel="pallas")
    assert base == pal
    ex = eng.stats()["executor"]
    assert ex["paged_kernel"] == "pallas"
    assert ex["kernel_invokes"]["pallas"] > 0
    assert ex["kernel_invokes"]["xla"] == 0


def test_engine_chunked_prefill_equals_whole(params):
    prompts = [_prompt(40, 14), _prompt(7, 15)]
    base, _ = _run_engine(params, prompts)
    for kern in ("xla", "pallas"):
        chunked, eng = _run_engine(params, prompts, prefill_chunk=16,
                                   paged_kernel=kern)
        assert chunked == base, kern
        assert eng.stats()["executor"]["chunk_prefills"] >= 3


def test_engine_chunked_prefill_interleaves_decode(params):
    """The ITL-bounding structure itself: while a long prompt is mid
    chunk-prefill, every engine step still advances the live decode
    batch — the long admit never stalls token production."""
    eng = LLMEngine(dict(params), n_heads=4, block_size=8,
                    num_blocks=64, max_batch=4, max_len=128,
                    prefill_chunk=8)
    short = eng.submit(_prompt(4, 16), max_new_tokens=32)
    eng.step()                       # short admits; its tokens in flight
    eng.step()                       # and read a step after their launch
    assert len(short.tokens) >= 1
    long_req = eng.submit(_prompt(48, 17), max_new_tokens=4)
    grew = []
    while long_req.state != "active" and eng.has_work:
        before = len(short.tokens)
        eng.step()
        grew.append(len(short.tokens) > before)
        assert long_req.state in ("prefilling", "active")
    # every chunk step also produced a decode token for the short req
    assert grew and all(grew)
    assert eng.executor.chunk_prefills >= 48 // 8
    eng.drain()
    assert long_req.finish_reason is not None


@pytest.mark.parametrize("kernel_fn", ["paged_prefill_attn",
                                       "paged_decode_attn"])
def test_pallas_build_failure_raises(params, monkeypatch, kernel_fn):
    """A Pallas kernel that cannot build is an error of the call that
    asked for it: the executor stays on `pallas`, counts no invoke on
    either kernel for the failed call, and never serves XLA instead."""
    from nnstreamer_tpu.backends import pallas_paged as pp
    from nnstreamer_tpu.core.errors import BackendError

    def refuse(*a, **k):
        raise NotImplementedError("Mosaic refuses this block shape")

    monkeypatch.setattr(pp, kernel_fn, refuse)
    eng = LLMEngine(dict(params), n_heads=4, block_size=8,
                    num_blocks=32, max_batch=2, max_len=64,
                    paged_kernel="pallas")
    eng.submit(_prompt(5, 18), max_new_tokens=3)
    with pytest.raises(BackendError, match="paged_kernel=pallas"):
        eng.drain()
    ex = eng.stats()["executor"]
    assert ex["paged_kernel"] == "pallas"
    assert "kernel_fallback" not in ex
    assert ex["kernel_invokes"]["xla"] == 0


def test_step_batches_prefill_syncs(params):
    """A step admitting many requests reads their first ids and the
    decode batch's ids with ONE forced device_sync, a step after it
    launched them (and waits for nothing in the step that launches);
    with a sampled row among them it resolves in the step itself, one
    sync for all the admissions and one for the decode batch."""
    from nnstreamer_tpu.runtime.sync import forced_sync_count

    eng = LLMEngine(dict(params), n_heads=4, block_size=8,
                    num_blocks=64, max_batch=4, max_len=64)
    for i in range(4):
        eng.submit(_prompt(5 + i, 20 + i), max_new_tokens=4)
    # absorb compile-time warm syncs by pre-compiling the buckets
    eng.prewarm(16)
    n0 = forced_sync_count()
    eng.step()                       # 4 admissions + 1 decode launch
    assert forced_sync_count() - n0 == 0
    eng.step()                       # next launch, then the read
    assert forced_sync_count() - n0 == 1
    eng.step()                       # steady state
    assert forced_sync_count() - n0 == 2
    eng.drain()
    for i in range(4):
        eng.submit(_prompt(5 + i, 20 + i), max_new_tokens=4,
                   temperature=0.7 if i == 2 else 0.0, seed=3)
    n1 = forced_sync_count()
    eng.step()                       # 4 admissions + 1 decode batch
    assert forced_sync_count() - n1 == 2
    n2 = forced_sync_count()
    eng.step()                       # steady state: decode only
    assert forced_sync_count() - n2 == 1
    eng.drain()


# -- metrics surface ---------------------------------------------------------

def test_llm_kernel_metrics_render(params):
    from nnstreamer_tpu.serving.metrics import (
        metrics_snapshot, parse_prometheus, render_prometheus)

    _, eng = _run_engine(params, [_prompt(6, 30)],
                         paged_kernel="pallas")
    text = render_prometheus(metrics_snapshot(
        llm={"llm0": eng.stats()}))
    fams = parse_prometheus(text)
    inv = fams["nns_llm_kernel_invokes_total"]
    assert inv["type"] == "counter"
    pallas_row = 'nns_llm_kernel_invokes_total' \
        '{element="llm0",kernel="pallas"}'
    assert inv["samples"][pallas_row] > 0
    assert "nns_llm_kernel_fallback_total" not in fams
    info = fams["nns_llm_paged_kernel_info"]["samples"]
    assert info[
        'nns_llm_paged_kernel_info{element="llm0",kernel="pallas"}'] \
        == 1.0


def test_tracer_kernel_spans(params):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tr = Tracer()
    eng = LLMEngine(dict(params), n_heads=4, block_size=8,
                    num_blocks=32, max_batch=2, max_len=64,
                    paged_kernel="pallas", tracer=tr)
    eng.submit(_prompt(5, 31), max_new_tokens=3)
    eng.drain()
    spans = tr.kernel_spans()
    assert spans.get(("llm", "pallas"), 0) > 0


# -- the pools keep their values in the type they were computed in ------------
#
# Under a bfloat16 model every value a step writes to a pool is a
# bfloat16 value already, so a float32 pool holds 16 bits of value and 16
# of zeros, and the same run on bfloat16 pools attends the same bits. The
# step functions take the pools as arguments: both arms run here, through
# the executor's own calls, on every path it serves.

POOL_PATHS = {
    "dense_xla": dict(paged_kernel="xla"),
    "dense_pallas": dict(paged_kernel="pallas"),
    "shards2_ring": dict(shards=2, ring_prefill_min=16),
    "sparse": dict(),
}
PROMPT_LEN, DECODE_STEPS = 32, 24


def _pool_executor(path, dtype=jnp.bfloat16, **kw):
    from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor

    geom = dict(block_size=8, num_blocks=16, max_len=64, dtype=dtype,
                name=f"pool_{path}")
    if path == "sparse":
        import tiny_sparse_moe as tiny
        from nnstreamer_tpu.backends.xla import ModelBundle
        from perfbench.references import sparse_moe_lm
        from perfbench.runners.sparse_moe_llm import lm_spec

        model = ModelBundle(
            fn=None, params=sparse_moe_lm.make_params(tiny.CONFIG, 77),
            lm=lm_spec(tiny.CONFIG))
    else:
        # the %8 geometry a sharded executor needs, for all three
        model = init_params(d_model=64, n_heads=8, n_layers=2, vocab=256,
                            seed=5)
        geom["n_heads"] = 8
    return PagedLLMExecutor(model, **geom, **POOL_PATHS[path], **kw)


def _pool_dtypes(ex):
    return {str(p.dtype) for p in ex.cache.pools()}


def _pool_run(ex, widen):
    """A 32-token prompt's prefill and 24 greedy decode steps. `widen`:
    on float32 pools in place of the executor's own. Returns (the 25
    logits, the pools as float32 numpy)."""
    if widen:
        ex.cache.set_pools([p.astype(jnp.float32)
                            for p in ex.cache.pools()])
    prompt = np.random.default_rng(2).integers(
        1, 200, PROMPT_LEN).astype(np.int32)
    table = ex.cache.allocator.alloc(
        ex.cache.blocks_for(PROMPT_LEN + DECODE_STEPS))
    logits = [np.asarray(ex.prefill(prompt, table))]
    for step in range(DECODE_STEPS):
        tok = int(np.argmax(logits[-1]))
        logits.append(np.asarray(
            ex.decode([tok], [table], [PROMPT_LEN + step])[0]))
    return np.stack(logits), [np.asarray(p.astype(jnp.float32))
                              for p in ex.cache.pools()]


@pytest.mark.parametrize("path", sorted(POOL_PATHS))
def test_bfloat16_pools_serve_a_bfloat16_model_bit_for_bit(
        path, eight_cpu_devices):
    narrow, wide = _pool_executor(path), _pool_executor(path)
    try:
        assert _pool_dtypes(narrow) == {"bfloat16"}
        lg_n, pools_n = _pool_run(narrow, widen=False)
        lg_w, pools_w = _pool_run(wide, widen=True)
        assert _pool_dtypes(wide) == {"float32"}
    finally:
        narrow.close()
        wide.close()
    assert len(pools_w) == (3 if path == "sparse" else 2)
    # (a) the float32 pools hold nothing bfloat16 does not
    for pool in pools_w:
        assert np.any(pool != 0.0)
        assert np.array_equal(
            pool, np.asarray(jnp.asarray(pool).astype(jnp.bfloat16)
                             .astype(jnp.float32)))
    # (b) the same logits at every step, the same pools after widening
    assert np.array_equal(lg_n, lg_w)
    for got, want in zip(pools_n, pools_w):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("path", sorted(POOL_PATHS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_executor_allocates_the_pools_in_its_dtype(path, dtype,
                                                   eight_cpu_devices):
    from nnstreamer_tpu.runtime.tracing import Tracer

    tracer = Tracer(max_events=4096)
    ex = _pool_executor(path, dtype=dtype, tracer=tracer)
    try:
        cache, size = ex.cache, jnp.dtype(dtype).itemsize
        assert _pool_dtypes(ex) == {jnp.dtype(dtype).name}
        assert len(cache.pools()) == (3 if path == "sparse" else 2)
        per_slot = 2 * ex.n_kv * ex.head_dim + cache.idx_dim
        assert cache.stats()["block_bytes"] == cache.block_bytes \
            == ex.n_layers * 8 * per_slot * size
        assert cache.resident_bytes() == 16 * cache.block_bytes
        assert ex.stats()["kv_pool_itemsize"] == size
        table = cache.allocator.alloc(2)
        prompt = np.arange(1, 10, dtype=np.int32)
        for _ in range(2):          # the second decode is not a compile
            ex.prefill(prompt, table)
            ex.decode([3], [table], [9])
        spans = [args for ph, cat, _, label, _, _, args in tracer.events()
                 if ph == "X" and cat == "backend" and label == "invoke"
                 and args.get("what") == "llm_decode"]
        assert spans and all(
            s["kv_pool_itemsize"] == size and s["kv_slots"] > 0
            for s in spans)
    finally:
        ex.close()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["itemsize2", "itemsize4"])
def test_walk_slots_equal_what_the_device_loop_gathers(params, monkeypatch,
                                                       dtype):
    """The executor's `kv_slots_read` is host arithmetic: it has to be
    the plan the step traces for the pool it is given. The extents are
    those of the float32 tile the products read, so the pool's
    itemsize does not move them."""
    from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor
    from nnstreamer_tpu.llm import parts

    monkeypatch.setattr(parts, "CHUNK_BYTES", 2 * WBLOCK_BYTES)
    monkeypatch.setattr(parts, "ITER_BYTES", 6 * WBLOCK_BYTES)
    plans = []
    live_items = parts.live_items

    def recording(tables, pos, block_size, nb_c, n_chunks, t):
        plans.append((nb_c, n_chunks, t))
        return live_items(tables, pos, block_size, nb_c, n_chunks, t)

    monkeypatch.setattr(parts, "live_items", recording)
    ex = PagedLLMExecutor(dict(params), n_heads=4, dtype=dtype,
                          block_size=WBS, num_blocks=WNB, max_len=WMB * WBS,
                          name="walk")
    try:
        rows = [37, 5, 20]
        tables = [ex.cache.allocator.alloc(p // WBS + 1) for p in rows]
        ex.decode([3, 4, 5], tables, rows)
        (nb_c, n_chunks, t), = plans        # what the step was traced with
        assert ex.cache.k.dtype.itemsize == jnp.dtype(dtype).itemsize
        assert (nb_c * WBS, t) == (16, 3)
        # a bucket of 4: the padding row's one chunk is gathered too. The
        # loop's trip count is a value of the step: compute it as it does
        pos = np.asarray(rows + [0], np.int32)
        n_iter = live_items(jnp.zeros((4, WMB), jnp.int32), jnp.asarray(pos),
                            WBS, nb_c, n_chunks, t)[3]
        assert int(n_iter) * t * nb_c * WBS == ex.stats()["kv_slots_read"] \
            == parts.walk_slots(pos, WBS, nb_c, t)
    finally:
        ex.close()
