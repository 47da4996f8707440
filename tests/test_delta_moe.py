"""The delta family (llm/delta_moe.py: layers of gated delta-rule linear
attention, a float32 state and three convolutions' tails a sequence by
slot, beside latent layers with no query rank and no rope over a pool by
block; a dense layer in front, a shared expert beside a share of routed
experts) against its plain reference in float32, through the cache, the
executor and the engine."""

import ast
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_delta_moe as tiny                                   # noqa: E402
import tiny_latent_moe                                          # noqa: E402
from nnstreamer_tpu.backends import pallas_state                # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import (                                # noqa: E402
    delta_moe, experts, families, latent_moe, parts)
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.paged_cache import PagedKVCache         # noqa: E402
from nnstreamer_tpu.llm.parts import norm, proj                 # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import delta_moe_lm as ref            # noqa: E402
from perfbench.references import latent_moe_lm                  # noqa: E402
from perfbench.runners import latent_moe_llm                    # noqa: E402
from perfbench.runners.delta_moe_llm import lm_spec             # noqa: E402

CFG = tiny.CONFIG
SPEC = lm_spec(CFG)
M = ref.dims(CFG)
SEED = 2**31 + 45
BS, CHUNK = 4, 8
POOL = dict(block_size=BS, num_blocks=80, max_len=64)
TOL = 1e-4          # float32 on the CPU: sums in another order only
# a KDA layer's state and tails a sequence: 3 layers x 2 heads x 8 x 8
# float32, and 3 layers x 3 inputs x 3 x 16 values float32
STATE, TAILS = 3 * 2 * 8 * 8 * 4, 3 * 3 * 48 * 4


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
    return LLMEngine(bundle, dtype=jnp.float32, **dict(
        dict(POOL, max_batch=4, prefill_chunk=CHUNK), **kw))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# -- the delta rule: the closed form over runs is the recurrence ------------------

def _rule_inputs(c, h=2, d=8, seed=0, real=None):
    """q, k, v, g, beta as `kda_inputs` gives them: q and k normed, g <=
    0 with some channels nearly forgotten in a run, beta in (0, 1); the
    tokens from `real` on are padding (g = 0, beta = 0)."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((c, h, d)).astype(np.float32)
               for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-7.0, 1.5, (c, h, d))).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, (c, h)).astype(np.float32)
    if real is not None:
        g[real:], beta[real:] = 0.0, 0.0
    state = rng.standard_normal((h, d, d)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, g, beta, state))


def _recurrence(q, k, v, g, beta, state):
    """The delta rule token by token from a carried state (the program's
    `delta_step` under `lax.scan`): the definition the closed form is
    checked against. Returns (o (C, H, dv), the state after)."""
    def one(s, xs):
        o, s = delta_moe.delta_step(*(x[None] for x in xs), s[None])
        return s[0], o[0]

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    return o, state


# run lengths that divide the chunk and that do not, a chunk shorter than
# a run, padding tokens at the end, one run for the whole chunk
@pytest.mark.parametrize("c,run,real", [
    (64, 16, None), (64, 64, None), (40, 16, None), (37, 5, None),
    (8, 64, None), (64, 16, 41), (24, 7, 3), (128, 64, 100)])
def test_the_closed_form_over_runs_is_the_recurrence(c, run, real):
    """From a carried state, in float32 to 1e-5: outputs of the real
    tokens and the state after the last of them."""
    q, k, v, g, beta, state = _rule_inputs(c, seed=c + run, real=real)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = delta_moe.delta_chunk(q, k, v, g, beta, state, run=run)
    n = c if real is None else real
    assert np.abs(np.asarray(got_o - want_o))[:n].max() < 1e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5
    assert float(jnp.abs(want_o).max()) > 0.05
    if real is not None:
        # padding tokens left the state alone: it is the real tokens'
        _, short = _recurrence(
            q[:real], k[:real], v[:real], g[:real], beta[:real], state)
        assert np.abs(np.asarray(got_s - short)).max() < 1e-5


def test_the_programs_recurrence_is_the_references():
    """`delta_step` under a scan against the reference's own scan, from
    a zero state (the reference has no other)."""
    q, k, v, g, beta, _ = _rule_inputs(48, seed=3)
    zero = jnp.zeros((2, 8, 8), jnp.float32)
    got, _ = _recurrence(q, k, v, g, beta, zero)
    want = ref.kda_recurrence(q, k, v, g, beta)
    assert np.abs(np.asarray(got - want)).max() < 1e-6


def test_a_strong_decay_neither_overflows_nor_divides():
    """Every exponent of the closed form is a difference G_i - G_j <= 0:
    channels that forget e^-40 a token stay finite and agree."""
    q, k, v, _, beta, state = _rule_inputs(64, seed=9)
    g = jnp.full((64, 2, 8), -40.0).at[:, :, ::2].set(-1e-4)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = delta_moe.delta_chunk(q, k, v, g, beta, state, run=32)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.abs(np.asarray(got_o - want_o)).max() < 1e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5


# -- the decode update's kernel: a row's state through its slot -------------------

def _pool_inputs(b, h, d, layers, slots, seed):
    """A bucket's q, k, v, g, beta (`_rule_inputs`, a token a row) and a
    state pool with nothing zero in it."""
    q, k, v, g, beta, _ = _rule_inputs(b, h=h, d=d, seed=seed)
    pool = np.random.default_rng(seed + 1).standard_normal(
        (layers, slots, h, d, d)).astype(np.float32)
    return (q, k, v, g, beta), jnp.asarray(pool)


def _rel(got, want):
    return np.abs(np.asarray(got) - np.asarray(want)).max() \
        / np.abs(np.asarray(want)).max()


# buckets of 1, 8 and 64 rows, the live rows fewer than the bucket (the
# padding rows on the scratch slot 0), the slots out of order, a layer in
# the middle of the pool, heads of 128 x 128 in groups of 4, 2 and 1
@pytest.mark.parametrize("b,n,hb", [
    (1, 1, 4), (8, 5, 2), (8, 8, 4), (64, 41, 2), (64, 64, 1)])
def test_the_state_kernel_is_the_gathered_rule(b, n, hb):
    """`pallas_state.delta_decode_update` (interpreted) against
    `delta_step` through a gather and a scatter by slot: the live rows'
    outputs and states to 1e-6 of the largest, zeros for the padding
    rows, and every slot without a live row and every other layer equal
    to what went in to the last bit."""
    layers, h, d, li = 3, 4, 128, 1
    n_slots = b + 2
    x, pool = _pool_inputs(b, h, d, layers, n_slots, seed=b + n)
    slots = np.zeros(b, np.int32)
    slots[:n] = np.random.default_rng(b).permutation(
        np.arange(1, n_slots))[:n]
    want_o, want_s = delta_moe.delta_step(*x, pool[li, slots])
    q, k, v, g, beta = x
    got_o, got = jax.jit(functools.partial(
        pallas_state.delta_decode_update, heads=hb))(
        q, k, v, jnp.exp(g), beta, pool, jnp.int32(li), jnp.asarray(slots),
        jnp.int32(n))
    assert got_o.shape == want_o.shape and got.shape == pool.shape
    assert _rel(got_o[:n], want_o[:n]) < 1e-6
    assert not np.asarray(got_o[n:]).any()
    got, pool = np.asarray(got), np.asarray(pool)
    assert _rel(got[li, slots[:n]], want_s[:n]) < 1e-6
    assert np.abs(got[li, slots[:n]] - pool[li, slots[:n]]).max() > 0.01
    idle = np.setdiff1d(np.arange(n_slots), slots[:n])
    assert 0 in idle
    assert np.array_equal(got[li, idle], pool[li, idle])
    assert np.array_equal(got[[0, 2]], pool[[0, 2]])


@pytest.mark.parametrize("d,hb", [(8, 2), (128, 1)])
def test_32_steps_through_the_kernel_are_the_recurrence(d, hb):
    """Two rows' sequences of 32 tokens, a kernel call a token, each on
    the pool the call before left, against `delta_step` under a scan from
    the states the slots held."""
    c, h, layers, li = 32, 2, 2, 1
    rows = [_rule_inputs(c, h=h, d=d, seed=70 + r) for r in range(2)]
    slots = jnp.asarray([2, 1], jnp.int32)
    pool = jnp.zeros((layers, 3, h, d, d), jnp.float32).at[li, slots].set(
        jnp.stack([r[5] for r in rows]))
    step = jax.jit(functools.partial(pallas_state.delta_decode_update,
                                     heads=hb))
    outs = []
    for t in range(c):
        q, k, v, g, beta = (jnp.stack([r[i][t] for r in rows])
                            for i in range(5))
        o, pool = step(q, k, v, jnp.exp(g), beta, pool, jnp.int32(li), slots,
                       jnp.int32(2))
        outs.append(o)
    for r, row in enumerate(rows):
        want_o, want_s = _recurrence(*row)
        assert np.abs(np.asarray(jnp.stack(outs)[:, r] - want_o)).max() < 1e-5
        assert np.abs(np.asarray(pool[li, slots[r]] - want_s)).max() < 1e-5
    assert not np.asarray(pool[0]).any() and not np.asarray(pool[li, 0]).any()


def test_the_state_kernel_refuses_a_pool_of_another_shape():
    (q, k, v, g, beta), pool = _pool_inputs(2, 4, 8, 2, 3, seed=5)
    args = (jnp.int32(0), jnp.asarray([1, 2], jnp.int32), jnp.int32(2))
    with pytest.raises(ValueError, match="groups of 3"):
        pallas_state.delta_decode_update(q, k, v, jnp.exp(g), beta, pool,
                                         *args, heads=3)
    with pytest.raises(ValueError, match="bfloat16"):
        pallas_state.delta_decode_update(
            q, k, v, jnp.exp(g), beta, pool.astype(jnp.bfloat16), *args)


def _wide_cfg():
    """The tiny configuration with KDA heads of 128, the width the rule
    takes (and the rank of the decay's and the gate's pairs with it)."""
    return dict(CFG, linear_attn_config=dict(CFG["linear_attn_config"],
                                             head_dim=128))


def test_the_state_rule_reads_the_backend_and_the_heads_width(monkeypatch):
    wide = lm_spec(_wide_cfg())
    assert not delta_moe.fused_state(wide)                  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_moe.fused_state(wide)
    assert delta_moe.fused_state(dataclasses.replace(wide, head_dim=256))
    assert not delta_moe.fused_state(dataclasses.replace(wide, head_dim=64))
    assert not delta_moe.fused_state(SPEC)                  # heads of 8


@pytest.mark.parametrize("how", ["fused", "gathered"])
def test_a_step_says_how_its_states_moved(monkeypatch, how):
    """The engine at KDA heads of 128, the rule forced either way (the
    kernel interpreted here): the reference's tokens both ways, every
    decode step counted under the way it took (`state_steps_fused` /
    `state_steps_gathered`) and saying it on its `invoke` span
    (`state_update`), `state_bytes_rw` what the rule needs either way."""
    cfg = _wide_cfg()
    params = ref.make_params(cfg, SEED, dtype=jnp.float32)
    monkeypatch.setattr(delta_moe, "fused_state", lambda spec: how == "fused")
    # a layer's trace does not know the rule it was made under
    delta_moe._decode_kda.clear_cache()
    calls = []
    monkeypatch.setattr(
        pallas_state, "delta_decode_update",
        lambda *a, _f=pallas_state.delta_decode_update, **k: (
            calls.append(a[5].shape), _f(*a, **k))[1])
    tracer = Tracer()
    eng = LLMEngine(ModelBundle(fn=None, params=params, lm=lm_spec(cfg)),
                    dtype=jnp.float32, max_batch=4, prefill_chunk=CHUNK,
                    tracer=tracer, **POOL)
    prompts = [_prompt(p, seed=i) for i, p in enumerate((20, 9, 13))]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.drain()
    delta_moe._decode_kda.clear_cache()
    for p, r in zip(prompts, reqs):
        ids = np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(ref.forward_logits(params, cfg, ids))[len(p) - 1:]
        assert list(want.argmax(-1)) == list(r.tokens)
    # a trace a bucket: the whole pool handed to the kernel, never a layer
    assert (len(calls) > 0) == (how == "fused")
    assert all(shape == (3, 5, 2, 128, 128) for shape in calls)
    st = eng.executor.stats()
    other = {"fused": "gathered", "gathered": "fused"}[how]
    assert st[f"state_steps_{how}"] == st["decode_steps"] > 0
    assert st[f"state_steps_{other}"] == 0
    decode = [e[6] for e in tracer.events() if e[3] == "invoke"
              and e[6].get("what") == "llm_decode"]
    assert decode and all(d["state_update"] == how for d in decode)
    assert all(d["state_bytes_rw"] == 2 * d["rows"] * 3 * 2 * 128 * 128 * 4
               for d in decode)


# -- the convolutions and their tails ---------------------------------------------

@pytest.mark.parametrize("cut", [1, 2, 3, 9, 16])
def test_a_chunk_from_a_carried_tail_is_the_whole_convolution(cut):
    """The sequence convolved whole from zeros (the reference's padded
    sum) against two pieces, the second from the first's last three
    inputs; a first piece shorter than the tail keeps zeros in front."""
    rng = np.random.default_rng(cut)
    x = jnp.asarray(rng.standard_normal((24, 12)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (4, 12)), jnp.float32)
    want = np.asarray(ref.conv_silu(x, w))
    seq = jnp.concatenate([jnp.zeros((3, 12)), x[:cut]])
    first = delta_moe.conv_act(seq, w, cut, jnp.float32)
    tail = seq[cut:]                        # the last three of (tail | x)
    rest = delta_moe.conv_act(jnp.concatenate([tail, x[cut:]]), w, 24 - cut,
                              jnp.float32)
    got = np.concatenate([np.asarray(first), np.asarray(rest)])
    assert np.abs(got - want).max() < 1e-6


# -- whole prompts, chunks, then decode, through the cache, on logits -----------

def _serve(ex, ids, plen, chunk=CHUNK, slot_of=None):
    """ids teacher-forced through the executor: the prompt's first `plen`
    in chunks of `chunk` (0: whole), the rest a decode step each. Returns
    the logits after positions plen - 1 .. len(ids) - 1."""
    cache = ex.cache
    blocks, slot = cache.reserve(cache.blocks_for(len(ids)))
    if slot_of is not None:
        slot_of.append(slot)
    if chunk:
        for at in range(0, plen, chunk):
            n = min(chunk, plen - at)
            lg = ex.prefill_chunk(ids[at:at + n], at, blocks, bucket=chunk,
                                  state_slot=slot)
    else:
        lg = ex.prefill(ids[:plen], blocks, state_slot=slot)
    out = [np.asarray(lg)]
    for t in range(plen, len(ids)):
        out.append(ex.decode([int(ids[t])], [blocks], [t],
                             state_slots=[slot])[0])
    cache.release(blocks, slot)
    return np.stack(out)


# a whole prompt in one bucket, a prompt in chunks whose last is short (its
# tail comes from two chunks), one that ends on a chunk's edge, a long one;
# under either form of the latent layers' chunk and either tile
@pytest.mark.parametrize("plen,total,chunk", [
    (5, 9, 0), (13, 20, 0), (12, 18, CHUNK), (16, 21, CHUNK),
    (33, 45, CHUNK), (10, 14, CHUNK)])
@pytest.mark.parametrize("expanded", [False, True])
def test_prompts_chunks_and_decode_give_the_references_logits(
        bundle, params, monkeypatch, plen, total, chunk, expanded):
    monkeypatch.setattr(latent_moe, "expanded_attend",
                        lambda c, spec: expanded)
    monkeypatch.setattr(parts, "CTX_TILE", 8 if expanded else 1024)
    # runs of 3 tokens: a chunk of 8 takes three, the last filled up
    monkeypatch.setattr(delta_moe, "RUN", 3 if expanded else 64)
    ids = _prompt(total, seed=plen)
    ex = _executor(bundle)
    got = _serve(ex, ids, plen, chunk)
    want = np.asarray(ref.forward_logits(params, CFG, ids))[plen - 1:]
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert ex.cache.allocator.used == 0 and ex.cache.state_alloc.used == 0
    said = ex.programs.stats()
    assert said["latents_expanded"] > 0 if expanded \
        else said["latents_expanded"] == 0


def test_a_new_owner_of_a_slot_starts_from_zero(bundle, params):
    """A slot given back and granted again: the next sequence's first
    chunk starts from a zero state and zero tails whatever the slot
    held, chunked or whole."""
    ex = _executor(bundle)
    slots = []
    first = _prompt(30, seed=1)
    _serve(ex, first, 20, slot_of=slots)
    held = [np.asarray(p)[:, slots[0]] for p in ex.cache.pools()[2:]]
    assert all(np.abs(h).max() > 0 for h in held)      # tails and states
    for chunk in (CHUNK, 0):
        ids = _prompt(19, seed=2 + chunk)
        got = _serve(ex, ids, 13, chunk, slot_of=slots)
        want = np.asarray(ref.forward_logits(params, CFG, ids))[12:]
        assert np.abs(got - want).max() < TOL
    assert len(set(slots)) == 1                        # the same slot thrice


def test_rows_keep_their_states_and_tails_apart(bundle, params):
    """Three sequences decoding in one bucket of four, each through its
    own slot; the padding row writes the scratch slot."""
    ex = _executor(bundle)
    cache = ex.cache
    seqs = [_prompt(n, seed=n) for n in (14, 23, 11)]
    plens = (9, 17, 5)
    held = []
    for ids, plen in zip(seqs, plens):
        blocks, slot = cache.reserve(cache.blocks_for(len(ids)))
        for at in range(0, plen, CHUNK):
            ex.prefill_chunk(ids[at:min(at + CHUNK, plen)], at, blocks,
                             bucket=CHUNK, state_slot=slot)
        held.append((blocks, slot))
    got = [[] for _ in seqs]
    for step in range(5):
        lg = ex.decode([int(ids[p + step]) for ids, p in zip(seqs, plens)],
                       [b for b, _ in held], [p + step for p in plens],
                       state_slots=[s for _, s in held])
        for i in range(3):
            got[i].append(lg[i])
    for i, (ids, plen) in enumerate(zip(seqs, plens)):
        want = np.asarray(ref.forward_logits(
            params, CFG, ids[:plen + 5]))[plen:plen + 5]
        assert np.abs(np.stack(got[i]) - want).max() < TOL, i
    assert len({s for _, s in held}) == 3 and 0 not in {s for _, s in held}


# -- the two kinds of cache ---------------------------------------------------------

def test_the_executor_builds_both_kinds_of_cache(bundle):
    ex = _executor(bundle)
    c = ex.cache
    k, idx, tails, state = c.pools()
    assert c.v is None and c.n_layers == 2             # the latent layers
    assert k.shape == (2, 80, BS, 1, 16)
    assert idx.shape == (2, 80, 1, 16)                 # 4 keys of 4 a row
    assert tails.shape == (3, 5, 1, 3 * 48) and tails.dtype == jnp.float32
    assert state.shape == (3, 5, 2, 8, 8) and state.dtype == jnp.float32
    st = c.stats()
    assert st["block_bytes"] == 2 * BS * (16 + 4) * 4
    assert st["state_slot_bytes"] == STATE + TAILS
    assert st["slot_row_bytes"] == TAILS and st["state_slots"] == 4
    assert ex.programs.state_bytes == STATE
    assert ex.programs.tail_bytes == TAILS
    # at the published widths, in bfloat16: what the issue reckons
    big = PagedKVCache(num_blocks=4, block_size=64, n_layers=2, n_kv=1,
                       head_dim=512, idx_dim=64, dtype=jnp.bfloat16,
                       values=False, state_slots=2,
                       state_shape=(6, 32, 128, 128),
                       row_shape=(6, 1, 9 * 4096))
    assert big.block_bytes == 64 * 2304
    assert big.state_slot_bytes == 12582912 + 442368
    assert big.stats()["slot_row_bytes"] == 442368
    assert big.state.dtype == jnp.float32
    assert big.slot_rows.dtype == jnp.bfloat16


def test_reserve_grants_blocks_and_a_slot_or_neither(bundle):
    c = _executor(bundle).cache
    assert c.state_alloc.total == 4
    grants = [c.reserve(2, owner=i) for i in range(4)]
    assert sorted(s for _, s in grants) == [1, 2, 3, 4]
    used = c.allocator.used
    assert c.reserve(2, owner="late") == "state"       # blocks there, no slot
    assert c.allocator.used == used and c.state_alloc.failed_allocs == 1
    c.release(*grants[0])
    # a slot free again, but the peak of the admitted rows would not fit
    assert c.reserve(2, owner="big", peak=80) == "blocks"
    assert c.state_alloc.used == 3
    blocks, slot = c.reserve(2, owner="next")
    assert slot == grants[0][1] and len(blocks) == 2


def test_admission_short_of_a_slot_is_counted_apart(bundle):
    """More rows than slots cannot be: the engine gives every row one.
    With the slots taken by hand the head of the queue waits, and the
    wait is counted under its own name and row state."""
    eng = _engine(bundle, max_batch=2)
    taken = [eng.cache.state_alloc.alloc(1)[0] for _ in range(2)]
    eng.submit(_prompt(6), max_new_tokens=3)
    for _ in range(3):
        eng.step()
    assert eng.admission_blocked_state == 3 and eng.admission_blocked == 0
    assert eng.stats()["cache"]["state_slots_used"] == 2
    eng.cache.state_alloc.free_blocks(taken)
    eng.drain()
    assert eng.finished == 1 and eng.cache.state_alloc.used == 0


# -- the engine -----------------------------------------------------------------

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
    cache = st["cache"]
    assert cache["blocks_used"] == 0 and cache["state_slots_used"] == 0
    assert cache["pools"] == 4
    ex = st["executor"]
    assert ex["family"] == "delta_moe"
    # 4 of 16 experts held: a quarter of the pairs where routing is even
    share = ex["expert_pairs_held"] / (ex["expert_pairs_held"]
                                       + ex["expert_pairs_away"])
    assert 0.05 < share < 0.6
    assert ex["state_bytes_rw"] == 2 * ex["state_rows"] * STATE
    assert ex["tail_bytes_rw"] == 2 * ex["state_rows"] * TAILS
    assert ex["kv_tokens_attended"] > 0 and ex["decode_steps_plain"] > 0
    assert ex["chunks_fresh"] == 8          # each request's first chunk
    assert sum(eng.rows[k] for k in eng.rows if k != "total") \
        == eng.rows["total"]


def test_the_reference_counts_the_programs_pairs(bundle, params):
    ids = _prompt(24, seed=9)
    ex = _executor(bundle)
    _serve(ex, ids, 24)
    taps = {}
    ref.forward_logits(params, CFG, ids, taps=taps)
    e = taps["experts"]                               # (4, 24, 4)
    held = int(((e >= 4) & (e < 8)).sum())
    st = ex.programs.stats()
    assert e.shape == (4, 24, 4)
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
    for key in ("rows", "state_rows", "state_bytes_rw", "tail_bytes_rw",
                "state_update", "kv_tokens", "kv_slots", "attend",
                "experts_touched",
                "expert_pairs_held", "expert_pairs_away"):
        assert key in decode[-1], key
    last = decode[-1]
    assert last["attend"] == "plain"                # the CPU's walk
    assert last["state_update"] == "gathered"       # and heads of 8
    assert last["state_rows"] == last["rows"]
    assert last["state_bytes_rw"] == 2 * last["rows"] * STATE
    assert last["tail_bytes_rw"] == 2 * last["rows"] * TAILS
    assert last["expert_pairs_held"] + last["expert_pairs_away"] \
        == last["rows"] * 4 * 4                # 4 a token, 4 expert layers
    # a bucket of at most 4 rows x 4 lies inside one row tile of 64: a
    # visit a touched expert
    assert last["expert_row_tile"] == 64
    assert last["expert_tile_visits"] == last["experts_touched"]
    for key in ("pos0", "clen", "fresh", "delta_runs", "ctx_tiles", "attend",
                "latents_expanded", *families.QBLOCK_KINDS):
        assert key in chunks[-1], key
    assert chunks[-1]["attend"] == "absorbed"       # a bucket of 8
    # (a bucket's first call is a `compile` span, not an `invoke`)
    assert all(c["fresh"] == (c["pos0"] == 0) for c in chunks)
    assert {c["fresh"] for c in chunks} == {True, False}
    assert chunks[-1]["delta_runs"] == 1            # 8 tokens, one run
    resolved = [e[6] for e in tracer.events() if e[3] == "resolve"]
    for key in ("req", "pos0", "clen", "expert_load_max",
                "expert_tile_visits", "expert_tile_fill_pct"):
        assert key in resolved[-1], key
    counters = eng.stats()["executor"]
    for key in ("state_rows", "state_bytes_rw", "tail_bytes_rw",
                "chunks_fresh", "delta_runs", "state_steps_fused",
                "state_steps_gathered", "decode_steps_fused",
                "decode_steps_plain", "chunk_prefills", "latents_expanded",
                "kv_tokens_attended", "kv_slots_read", *families.QBLOCK_KINDS,
                *families.EXPERT_COUNTERS):
        assert key in counters, key
    assert counters["expert_tile_rows"] \
        == 64 * counters["expert_tile_visits"] > 0
    cache = eng.stats()["cache"]
    assert {"state_slots_used", "state_slot_bytes", "block_bytes"} <= set(
        cache)


# -- the expert layer: the chip's share, and the router ---------------------------

def _uncut():
    return dict(CFG, num_experts=16,
                expert_share={"published": 16, "first": 0})


def test_the_four_chips_parts_add_up_to_the_uncut_layer():
    """What each of the four chips' held experts add, and the shared
    expert and everything outside the expert layer once, is the uncut
    reference's layer: the guide's test of a cut by the chip's share."""
    whole = ref.make_params(_uncut(), SEED, dtype=jnp.float32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, 64)),
                    jnp.float32)
    full, _ = ref.routed_part(u, whole, ref.dims(_uncut()))
    shares = []
    for chip in range(4):
        first = 4 * chip
        share = dict(whole, ewi=whole["ewi"][first:first + 4],
                     ewd=whole["ewd"][first:first + 4])
        shares.append(ref.routed_part(u, share, dict(M, first=first))[0])
        # the program's layer, told the same share
        spec = dataclasses.replace(SPEC, experts_first=first)
        y, counts, away = experts.expert_layer(
            share, u, jnp.ones((24,), bool), spec, jnp.float32)
        assert np.abs(np.asarray(y) - np.asarray(shares[-1])).max() < TOL
        assert int(counts.sum()) + int(away) == 24 * 4
    assert np.abs(np.asarray(sum(shares)) - np.asarray(full)).max() < TOL
    assert float(jnp.abs(full).max()) > 0.1
    # the whole layer (a KDA one): the mixer and the shared expert once
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 64)), jnp.float32)
    uncut = ref._Static(ref.dims(_uncut()))
    kw = dict(kind=ref.KDA, quant=None, q_block=8)
    y_full, _ = ref._layer(x, whole, m=uncut, **kw)
    y_none, _ = ref._layer(x, dict(whole, ewi=whole["ewi"][:4] * 0,
                                   ewd=whole["ewd"][:4] * 0), m=uncut, **kw)
    routed = sum(
        ref._layer(x, dict(whole, ewi=whole["ewi"][4 * c:4 * c + 4],
                           ewd=whole["ewd"][4 * c:4 * c + 4]),
                   m=ref._Static(dict(uncut, first=4 * c)), **kw)[0] - y_none
        for c in range(4))
    assert np.abs(np.asarray(y_none + routed) - np.asarray(y_full)).max() \
        < TOL


def test_router_ties_go_to_the_lower_index():
    """Equal scores + bias: the lower index is chosen, by the program's
    router and by the reference's; the bias is in the choice and not in
    the weights, which are renormalised and scaled."""
    d = 64
    router = jnp.zeros((d, 16), jnp.float32)           # every score 0.5
    bias = jnp.zeros((16,), jnp.float32).at[jnp.array([9, 3, 12])].set(0.1)
    blk = {"router": router, "router_bias": bias}
    u = jnp.ones((3, d), jnp.float32)
    p, e = experts.route(blk, u, SPEC, jnp.float32)
    # the three with a bias, then the lowest index among the tied others
    assert np.asarray(e).tolist() == [[3, 9, 12, 0]] * 3
    assert np.allclose(np.asarray(p), 2.446 / 4)
    want_p, want_e = ref.route(u, blk, M)
    assert np.array_equal(np.asarray(want_e), np.asarray(e))
    assert np.allclose(np.asarray(want_p), np.asarray(p))


# -- the latent family, told there is no rank and no rope --------------------------

def test_the_latent_familys_projection_is_the_parents_bit_for_bit():
    """DeepSeek-V2's family is unchanged by the two static branches: its
    spec is ranked and roped, and `_project` gives, bit for bit, what the
    lines it replaced gave (the parent's, written out here)."""
    cfg = tiny_latent_moe.CONFIG
    spec = latent_moe_llm.lm_spec(cfg)
    assert spec.q_rank == 24 and spec.roped
    blk = latent_moe_lm.make_params(cfg, SEED, dtype=jnp.float32)["blocks"][1]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(7, 1, 64)),
                    jnp.float32)
    pos = jnp.arange(7) + 11
    dtype = jnp.float32
    u = norm(blk["ln1"], x, spec, dtype)
    cq = norm(blk["q_norm"], proj(blk, "wqa", u, dtype), spec, dtype)
    q = proj(blk, "wqb", cq, dtype).reshape(7, 4, 12)
    kv = proj(blk, "wkva", u, dtype)[:, 0]
    c = norm(blk["kv_norm"], kv[:, :16], spec, dtype)
    want = (q[..., :8], latent_moe._rope(q[..., 8:], pos, spec), c,
            latent_moe._rope(kv[:, 16:], pos, spec))
    got = latent_moe._project(blk, x, pos, spec, dtype)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    # and this family's: no rank (one matrix, no norm), nothing turned
    blk = ref.make_params(CFG, SEED, dtype=jnp.float32)["blocks"][2]
    u = norm(blk["ln1"], x, SPEC, dtype)
    q = proj(blk, "wq", u, dtype).reshape(7, 4, 12)
    kv = proj(blk, "wkva", u, dtype)[:, 0]
    got = latent_moe._project(blk, x, pos, SPEC, dtype)
    assert np.array_equal(np.asarray(got[1]), np.asarray(q[..., 8:]))
    assert np.array_equal(np.asarray(got[3]), np.asarray(kv[:, 16:]))
    assert latent_moe.score_scale(SPEC) == pytest.approx(12 ** -0.5)


def test_the_latent_familys_decode_step_traces_as_the_parents(monkeypatch):
    """The same jaxpr for DeepSeek-V2's tiny decode step whether the two
    helpers are called or their bodies stand inline, as in the parent."""
    cfg = tiny_latent_moe.CONFIG
    spec = latent_moe_llm.lm_spec(cfg)
    params = latent_moe_lm.make_params(cfg, SEED, dtype=jnp.float32)
    cache = PagedKVCache(num_blocks=8, block_size=4, n_layers=3, n_kv=1,
                         head_dim=16, idx_dim=4, dtype=jnp.float32,
                         values=False)
    args = (params, jnp.zeros((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32),
            jnp.array([3, 5], jnp.int32), jnp.int32(2), *cache.pools())

    def trace():
        # the inner jits keep their traces: build the step anew
        jax.clear_caches()
        return str(jax.make_jaxpr(lambda *a: latent_moe.latent_moe_decode_step(
            *a, spec=spec, dtype=jnp.float32))(*args))

    now = trace()
    monkeypatch.setattr(latent_moe, "_ranked", lambda blk, u, spec, dtype: norm(
        blk["q_norm"], proj(blk, "wqa", u, dtype), spec, dtype))
    monkeypatch.setattr(latent_moe, "_turn", latent_moe._rope)
    assert trace() == now and "wqa" not in now


# -- what the family refuses -------------------------------------------------------

def test_refusals(bundle, params):
    with pytest.raises(BackendError, match="paged_kernel=pallas.*fused_state"):
        _executor(bundle, paged_kernel="pallas")
    with pytest.raises(BackendError, match="shards=2.*by slot on one chip"):
        LLMEngine(bundle, dtype=jnp.float32, shards=2, **POOL)
    blocks = [dict(params["blocks"][0], wqkv_scale=jnp.ones((1,)))] \
        + params["blocks"][1:]
    with pytest.raises(BackendError, match="W8A8.*float32"):
        _executor(ModelBundle(fn=None, params=dict(params, blocks=blocks),
                              lm=SPEC))

    def refused(match, **changed):
        with pytest.raises(BackendError, match=match):
            _executor(ModelBundle(fn=None, params=params,
                                  lm=dataclasses.replace(SPEC, **changed)))

    refused("layers under a spec that names 4",
            layer_kinds=SPEC.layer_kinds[:4])
    refused("layers of both kinds", layer_kinds=("kda",) * 5)
    refused("convolution over at least 2", conv_kernel=1)
    refused("at least one layer", dense_layers=5)
    # a query with a rank needs the matrices this bundle does not carry
    refused("q_rank=8.*needs wqa and q_norm and wqb.*no q_norm, wqa, wqb",
            q_rank=8)
    # the latent family says the same of a bundle without `wq`
    cfg = tiny_latent_moe.CONFIG
    spec = latent_moe_llm.lm_spec(cfg)
    with pytest.raises(BackendError, match="q_rank=0.*needs wq .*no wq"):
        PagedLLMExecutor(
            ModelBundle(fn=None, lm=dataclasses.replace(spec, q_rank=0),
                        params=latent_moe_lm.make_params(
                            cfg, SEED, dtype=jnp.float32)),
            dtype=jnp.float32, **POOL)
    # a whole prompt past one chunk's reach needs chunked prefill
    eng = LLMEngine(bundle, dtype=jnp.float32, block_size=4, num_blocks=2000,
                    max_len=6000)
    with pytest.raises(BackendError, match="needs chunked prefill"):
        eng.submit(_prompt(5000), max_new_tokens=4)


def test_the_module_stands_on_the_latent_familys_public_names():
    """`llm/delta_moe.py` imports `parts`, `experts` and, of the family
    modules, `latent_moe` alone, by public names (the rule itself:
    tests/test_llm_parts.py)."""
    with open(delta_moe.__file__) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            mods |= {f"{node.module}.{a.name}" for a in node.names}
    llm = {m.split(".")[2] for m in mods
           if m.startswith("nnstreamer_tpu.llm.")}
    assert llm == {"latent_moe", "parts", "experts", "spec"}
    used = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name) and n.value.id == "latent_moe"}
    assert used == {"fused_decode", "walk_plan", "decode_layer",
                    "chunk_layer"}
    assert families.FAMILIES["delta_moe"] is families.DeltaMoESet
    assert issubclass(families.DeltaMoESet, families.LatentMoESet)
