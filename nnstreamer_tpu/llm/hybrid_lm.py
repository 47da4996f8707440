"""The decoder whose layers are linear attention with a carried state or
block-sparse attention over paged KV (pure jax, jitted by llm_exec as
``jit_hybrid_decode_step`` and ``jit_hybrid_prefill_chunk``).

`LMSpec.layer_kinds` says which kind each layer is. The projections
(`proj`), the rope (`rope_rows`), every layer's SwiGLU (`mlp_paged`), the
head (`finish`, with this family's `logit_div`) and a layer's index among
its kind (`layer_index`) are `llm/parts.py`'s, as are the pieces of the
chunk's walk named below. Three scalings ride the residual stream: the
embedding is multiplied by ``spec.emb_scale``, each branch by
``spec.residual_scale`` before it is added, and the final norm's output
is divided by ``spec.logit_div``.

A LINEAR layer (``spec.lin_heads`` heads of ``head_dim``) keeps no keys:
per head j a state S (hd x hd, float32) that forgets by
``lam_j = exp(-slope_j)``, ``slope_j = 2^(-8 (j + 1) / H)``, a token:
``S_t = lam_j S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(hd)) S_t``, q and
k per-head normed and roped; ``y = Wo (sigmoid(h Wg) * rmsnorm(o))``.

- Decode: a row's state is read from its *slot* of the state pool
  ``(linear layers, slots, H, hd, hd)``, advanced by one token and
  written back in place.
- Chunk: the chunk's tokens in runs of `_SCAN` tokens; within a run the
  masked quadratic form ``(Q K^T * D) V`` with ``D_ij = lam^(i-j)``, from
  the run before it ``diag(lam^(i+1)) Q S``, and the state handed on is
  ``lam^n S + sum_i lam^(n-1-i) k_i^T v_i``. Padding tokens (past the
  chunk's last real one) add nothing and age nothing: the decay counts
  real tokens. A chunk at position 0 starts from zero, whatever the slot
  held.

A SPARSE layer (``n_heads`` query, ``n_kv`` key/value heads, no rope) keeps
K and V in the paged pools, *a KV head a pool layer*: sparse layer `li`'s
head g is layer ``li * n_kv + g`` of pools shaped ``(sparse layers * n_kv,
num_blocks, block_size, 1, hd)``, so that what one head selects is whole
blocks of its own (gathering one head's half of a block that holds both
made XLA:TPU re-lay the whole 3.4 GB pool; compiled text, PR 31). Beside
them it keeps one *compressed key* a KV head and block of the table:
entry m is the mean of the keys of blocks m ... m + r - 1 (``r = ck_kernel /
ck_stride``; the pool's block size is the stride), written by the chunk or
the decode step that writes the last of those keys. They live by the
sequence's *slot*, ``(sparse layers * n_kv, slots, max_blocks, hd)``, not by
block: every query reads all of its sequence's compressed keys, in order,
and gathered a block at a time (256-byte rows) a decode step of 32 rows
spent 3 ms a layer on 68 MB (my chip run, PR 31).

A query at position t scores the compressed keys that are complete by t
(softmax over them, summed over the query heads of a KV head), a selection
block of ``sel_block`` tokens takes the largest score of the compressed keys
whose tokens overlap it, the first ``sel_init`` blocks and the ``sel_window
/ sel_block`` blocks ending in the query's own are forced, and the query
attends the ``sel_topk`` highest blocks, ties to the lower index, causally.
No ``(queries, max_len)`` score over tokens exists; the scores over
compressed keys are ``(heads, queries, max_len / stride)``, a tile of
`_Q_TILE` queries at a time in a chunk.

- Decode: `jax.lax.top_k` over a row's block scores; all ``sel_topk``
  blocks gathered, a row and KV head.
- Chunk: the selection of every query is kept as a mask over blocks,
  forced and chosen as one set (`attended_mask`; the chosen ones found
  without a sort, `chosen_mask`), and attention walks the live context a
  tile of `parts.CTX_TILE` slots at a time, a loop whose trip count
  comes from ``pos0`` (`sparse_attend_walk`, `parts.tile_span`): a tile's
  K and V are read once through the table for all of the chunk's queries,
  a query's block bits are spread over the tile's slots behind its
  position, and an online softmax's carry is updated under that mask, on
  a TPU in one kernel a tile and KV head
  (`pallas_ops.selected_block_update`; elsewhere `parts.attend_plain`).
  The loop is this family's own (`parts.walk_tiles` has one carry, this
  one a KV head, since heads select apart). Nothing a query wide is
  gathered: a context of C tokens is read once, not once for each of the
  queries that chose from it. The walk's time grows with
  the context, 0.39 ms a tile and layer on the v5e (PERF.md, PR 36).

``y = Wo (sigmoid(h Wg) * o)``.

All pools hold the compute type's values in the compute type except the
linear layers' state, which is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.llm.parts import (
    finish, layer_index, mlp_paged, proj, rope_rows)
from nnstreamer_tpu.llm.spec import LINEAR, LMSpec
from nnstreamer_tpu.models.transformer import rmsnorm

# Tokens of a chunk the linear layer takes at a time: the (heads, run,
# run) float32 decay and scores of a run are 8.4 MB each at 32 heads.
_SCAN = 256
# Queries of a chunk the sparse layer scores and selects for at a time: a
# tile's scores over the compressed keys are tile x heads x max_blocks
# float32.
_Q_TILE = 128

_F32 = jnp.float32


def decay_slopes(n_heads: int):
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=_F32) / n_heads)


# -- the linear layer ---------------------------------------------------------

def _linear_qkv(blk, h, pos, spec: LMSpec, dtype):
    """h (N, 1, D) normed input at positions pos (N,): q, k, v
    (N, H, hd), q and k normed and roped."""
    n = h.shape[0]
    nh, hd = spec.lin_heads, spec.head_dim
    qkv = proj(blk, "wqkv", h, dtype).reshape(n, 1, 3, nh, hd)
    q = rmsnorm(qkv[:, :, 0], blk["q_norm"].astype(dtype))
    k = rmsnorm(qkv[:, :, 1], blk["k_norm"].astype(dtype))
    q = rope_rows(q, pos, spec.rope_theta)
    k = rope_rows(k, pos, spec.rope_theta)
    return q[:, 0], k[:, 0], qkv[:, 0, 2]


def linear_scan(q, k, v, live, state, dtype):
    """A chunk's linear attention: q, k, v (C, H, hd) in `dtype`, live
    (C,) bool (the real tokens, first), state (H, hd, hd) float32 before
    the chunk. Returns (o (C, H, hd) float32, the state after the last
    real token)."""
    c, nh, hd = q.shape
    run = min(_SCAN, c)
    slopes = decay_slopes(nh)
    causal = jnp.arange(run)[:, None] >= jnp.arange(run)[None, :]

    def one_run(state, xs):
        qs, ks, vs, ls = xs
        # real tokens up to and with each: what the decay counts
        age = jnp.cumsum(ls.astype(_F32))
        ks = jnp.where(ls[:, None, None], ks, jnp.zeros_like(ks))
        decay = jnp.where(causal[None], jnp.exp(
            -slopes[:, None, None] * (age[:, None] - age[None, :])[None]),
            0.0)                                             # (H, run, run)
        sc = jnp.einsum("ihd,jhd->hij", qs, ks,
                        preferred_element_type=_F32) * decay * hd ** -0.5
        o = jnp.einsum("hij,jhd->ihd", sc.astype(dtype), vs,
                       preferred_element_type=_F32)
        fade = jnp.exp(-slopes[None, :] * age[:, None])      # (run, H)
        o = o + jnp.einsum(
            "ihd,hde->ihe",
            qs.astype(_F32) * (fade * hd ** -0.5)[..., None], state)
        left = jnp.exp(-slopes[None, :] * (age[-1] - age)[:, None])
        state = jnp.exp(-slopes * age[-1])[:, None, None] * state \
            + jnp.einsum("jhd,jhe->hde",
                         ks.astype(_F32) * left[..., None], vs.astype(_F32))
        return state, o

    def runs(x):
        return x.reshape((c // run, run) + x.shape[1:])

    state, o = jax.lax.scan(one_run, state,
                            (runs(q), runs(k), runs(v), runs(live)))
    return o.reshape(c, nh, hd), state


def _gated_out(blk, x, h, o, spec: LMSpec, dtype):
    """x + r * Wo (sigmoid(h Wg) * o) for a mixer's output o (N, H, hd),
    normed over all its values first where the layer has an output norm
    (the linear layers)."""
    o = o.reshape(x.shape[0], 1, -1).astype(dtype)
    if "o_norm" in blk:
        o = rmsnorm(o, blk["o_norm"].astype(dtype))
    gate = jax.nn.sigmoid(proj(blk, "wg", h, dtype))
    return x + spec.residual_scale * proj(blk, "wo", gate * o, dtype)


# -- the sparse layer ---------------------------------------------------------

def _sparse_qkv(blk, h, spec: LMSpec, dtype):
    """h (N, 1, D) -> q (N, H, hd), k and v (N, G, hd); q and k normed,
    no rope."""
    n = h.shape[0]
    nh, g, hd = spec.n_heads, spec.n_kv, spec.head_dim
    qkv = proj(blk, "wqkv", h, dtype)
    qw, kw = nh * hd, g * hd
    q = rmsnorm(qkv[..., :qw].reshape(n, nh, hd), blk["q_norm"].astype(dtype))
    k = rmsnorm(qkv[..., qw:qw + kw].reshape(n, g, hd),
                blk["k_norm"].astype(dtype))
    return q, k, qkv[..., qw + kw:].reshape(n, g, hd)


def _block_scores(p, spec: LMSpec):
    """p (..., M) a compressed key (-1 where it is not complete) ->
    (..., M * stride / sel_block): a selection block's largest p over the
    compressed keys whose tokens overlap it."""
    per = spec.sel_block // spec.ck_stride
    extra = (spec.ck_kernel - 1) // spec.ck_stride
    n = -(-p.shape[-1] // per)
    padded = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(extra, 2 * per)],
                     constant_values=-1.0)
    return functools.reduce(jnp.maximum, (
        padded[..., i::per][..., :n] for i in range(per + extra)))


def _forced(nb: int, qpos, spec: LMSpec):
    """(forced (N, NB), own (N, 1)): the blocks every query at qpos (N,)
    attends whatever their score (the first sel_init and the window
    ending in its own), and its own block."""
    b = jnp.arange(nb)[None, :]
    own = (qpos // spec.sel_block)[:, None]
    return (b < spec.sel_init) | (
        b > own - spec.sel_window // spec.sel_block), own


def select_blocks(score, qpos, spec: LMSpec):
    """score (N, G, NB) of queries at positions qpos (N,). Returns
    (blocks (N, G, K) int32, valid (N, G, K)): the K = sel_topk selection
    blocks of largest score among those up to the query's own, the
    forced ones first; `valid` is false where the sequence has fewer
    than K."""
    nb = score.shape[-1]
    forced, own = _forced(nb, qpos, spec)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((jnp.arange(nb)[None, :] <= own)[:, None, :], score,
                      -jnp.inf)
    top, blocks = jax.lax.top_k(score, min(spec.sel_topk, nb))
    return blocks, top > -jnp.inf


def _n_chosen(spec: LMSpec) -> int:
    """The blocks a query chooses by score: sel_topk less the forced."""
    return spec.sel_topk - spec.sel_init - spec.sel_window // spec.sel_block


def chosen_mask(score, qpos, spec: LMSpec):
    """The same selection without its forced blocks, for a chunk's many
    queries, as a mask: of the blocks a query is not forced to, the
    sel_topk - sel_init - window blocks of largest score, ties to the
    lower index. Returns (chosen (N, G, NB) bool, take (N,): how many a
    query chose). No sort: the J-th largest score of each row is found
    by the search over the bits of order-preserving keys
    (`parts.select_cut`)."""
    n, g, nb = score.shape
    forced, own = _forced(nb, qpos, spec)
    free = ~forced & (jnp.arange(nb)[None, :] <= own)            # (N, NB)
    take = jnp.minimum(_n_chosen(spec), jnp.sum(free, axis=1))   # (N,)
    keys = jnp.where(free[:, None, :], parts.sort_keys(score),
                     jnp.uint32(0))
    keys = keys.reshape(n * g, nb)
    rows = jnp.repeat(take, g)
    t, cut = parts.select_cut(keys, 1, nb, rows)
    sel = (keys > t[:, None]) | ((keys == t[:, None]) & (
        jnp.arange(nb)[None, :] <= cut[:, None]))
    sel = sel & (rows > 0)[:, None]
    return sel.reshape(n, g, nb), take


def attended_mask(score, qpos, spec: LMSpec):
    """(N, G, NB) bool: the selection blocks a query attends, forced and
    chosen as one set (blocks past its own among them: the position
    cuts those, slot by slot)."""
    forced, _ = _forced(score.shape[-1], qpos, spec)
    if _n_chosen(spec) <= 0:
        return jnp.broadcast_to(forced[:, None, :], score.shape)
    return forced[:, None, :] | chosen_mask(score, qpos, spec)[0]


def _head_layers(li, spec: LMSpec):
    """The pool layers of sparse layer `li`'s KV heads (G,)."""
    return li * spec.n_kv + jnp.arange(spec.n_kv)


def _score_blocks(qg, qpos, ck, spec: LMSpec):
    """Queries qg (N, G, R, hd) at positions qpos (N,) against the
    compressed keys ck, one set for all (G, M, hd) or one each
    (N, G, M, hd): each selection block's score, (N, G, NB)."""
    hd = qg.shape[-1]
    sc = jnp.einsum("ngrd,gmd->ngrm" if ck.ndim == 3 else "ngrd,ngmd->ngrm",
                    qg, ck, preferred_element_type=_F32) * hd ** -0.5
    done = (spec.ck_stride * jnp.arange(ck.shape[-2]) + spec.ck_kernel
            - 1)[None, :] <= qpos[:, None]                       # (N, M)
    sc = jnp.where(done[:, None, None, :], sc, -1e30)
    e = jnp.where(done[:, None, None, :],
                  jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = jnp.where(done[:, None, :], jnp.sum(p, axis=2), -1.0)   # (N, G, M)
    return _block_scores(p, spec)


def _softmax_over(sc, may, dtype):
    """Softmax of scores sc (..., T) f32 over the places `may` allows:
    weights in `dtype`, 0 elsewhere."""
    w = jax.nn.softmax(jnp.where(may, sc, -1e30), axis=-1)
    return jnp.where(may, w, 0.0).astype(dtype)


def _gathered(pool, head, pool_blk, dtype):
    """Whole blocks of each KV head's own pool layer: pool_blk (N, G, J)
    -> (N, G, J * block_size, hd)."""
    x = pool[head[None, :, None], pool_blk].astype(dtype)
    return x.reshape(x.shape[:2] + (-1, x.shape[-1]))


def sparse_attend_rows(q, qpos, tables, slots, li, k_pool, v_pool, c_pool,
                       *, spec: LMSpec, dtype):
    """Layer `li`'s attention of a decode batch: queries q (B, H, hd) at
    positions qpos (B,), each through its own table (B, MB) and its own
    slot's compressed keys. Every selected block is gathered, sel_topk a
    row and KV head. Returns o (B, H, hd) in `dtype`."""
    n, nh, hd = q.shape
    g = spec.n_kv
    bs = k_pool.shape[2]
    qg = q.reshape(n, g, nh // g, hd)
    head = _head_layers(li, spec)
    ck = c_pool[head[None, :], slots[:, None]].astype(dtype)  # (B, G, MB, hd)
    blocks, valid = select_blocks(_score_blocks(qg, qpos, ck, spec), qpos,
                                  spec)                         # (B, G, K)
    # a selection block is `per` pool blocks in a row of the table
    per = spec.sel_block // bs
    entry = (blocks[..., None] * per + jnp.arange(per)).reshape(n, g, -1)
    valid = jnp.repeat(valid, per, axis=-1)                     # (B, G, K*per)
    pool_blk = jnp.take_along_axis(tables[:, None, :], entry, axis=-1)
    pool_blk = jnp.where(valid, pool_blk, 0)         # the scratch block
    kc = _gathered(k_pool, head, pool_blk, dtype)
    vc = _gathered(v_pool, head, pool_blk, dtype)
    tok = (entry[..., None] * bs + jnp.arange(bs)).reshape(n, g, -1)
    may = jnp.repeat(valid, bs, axis=-1) & (tok <= qpos[:, None, None])
    att = jnp.einsum("ngrd,ngtd->ngrt", qg, kc,
                     preferred_element_type=_F32) * hd ** -0.5
    w = _softmax_over(att, may[:, :, None, :], dtype)
    o = jnp.einsum("ngrt,ngtd->ngrd", w, vc, preferred_element_type=_F32)
    return o.reshape(n, nh, hd).astype(dtype)


def sparse_attend_walk(q, qpos, mask, tab, n_tiles, li, k_pool, v_pool,
                       *, spec: LMSpec, dtype, fused: bool, tile: int):
    """Layer `li`'s attention of a whole chunk: queries q (C, H, hd) at
    positions qpos (C,) of one sequence, each attending the selection
    blocks mask (C, G, NB) names (`attended_mask`), causally. The live
    context is walked a tile of `tile` slots at a time, `n_tiles` of
    them (a traced count): a tile's K and V are read once
    through the table `tab` (MB,) for all queries, a KV head from its
    own pool layer, the tile's block bits of a query are spread over its
    slots behind the query's position, and an online softmax's carry is
    updated under that mask: by `pallas_ops.selected_block_update` where
    `fused` (`parts.fused_attend`), a call a KV head since heads
    select apart, else by `parts.attend_plain`. Nothing a query
    wide is gathered. Returns o (C, H, hd) in `dtype`."""
    c, nh, hd = q.shape
    g, sb = spec.n_kv, spec.sel_block
    grp = nh // g
    bs = k_pool.shape[2]
    if tile % sb:
        raise ValueError(f"sel_block {sb} does not divide the context tile "
                         f"of {tile} slots")
    tab = parts.whole_tiles(tab, tile, bs)
    nb_t, sb_t = tile // bs, tile // sb
    max_tiles = tab.shape[0] // nb_t
    mask = jnp.pad(mask.transpose(1, 0, 2),
                   ((0, 0), (0, 0), (0, max_tiles * sb_t - mask.shape[2])))
    head = _head_layers(li, spec)
    qg = q.reshape(c, g, 1, grp, hd)
    # a KV head's queries as its update takes them: made once
    qs = [qg[:, i].transpose(1, 2, 0, 3) if fused else qg[:, i]
          for i in range(g)]
    slot = jnp.arange(tile)
    # selection keys of 1 and 0 under a threshold of 0 with no tie taken
    none, no_tie = jnp.zeros((c,), jnp.uint32), jnp.full((c,), -1, jnp.int32)

    def attend_tile(j, state):
        bl = jax.lax.dynamic_slice_in_dim(tab, j * nb_t, nb_t)
        kt = k_pool[head[:, None], bl[None, :]].astype(dtype)
        vt = v_pool[head[:, None], bl[None, :]].astype(dtype)
        kt, vt = kt.reshape(g, tile, 1, hd), vt.reshape(g, tile, 1, hd)
        on = jax.lax.dynamic_slice_in_dim(mask, j * sb_t, sb_t, 2)
        on = jnp.repeat(on, sb, axis=2) & (
            (j * tile + slot)[None, :] <= qpos[:, None])[None]
        keys = on.astype(jnp.uint32)                        # (G, C, tile)
        if fused:
            return tuple(pallas_ops.selected_block_update(
                qs[i], kt[i], vt[i], keys[i], none, no_tie, 0, *state[i],
                block_q=parts.FUSED_Q_BLOCK) for i in range(g))
        return tuple(parts.attend_plain(
            qs[i], kt[i], vt[i], keys[i], none, no_tie, 0, state[i])
            for i in range(g))

    state = jax.lax.fori_loop(0, n_tiles, attend_tile, tuple(
        (jnp.full((1, grp, c), -1e30, _F32), jnp.zeros((1, grp, c), _F32),
         jnp.zeros((1, grp, c, hd), _F32)) for _ in range(g)))
    att = jnp.concatenate([acc / l[..., None] for _, l, acc in state])
    return att.transpose(2, 0, 1, 3).reshape(c, nh, hd).astype(dtype)


def _mlp(blk, x, spec: LMSpec, dtype):
    h = rmsnorm(x, blk["ln2"].astype(dtype))
    return x + spec.residual_scale * mlp_paged(blk, h, dtype)


# -- decode -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _decode_linear(blk, x, li, pos, slots, s_pool, *, spec, dtype):
    """Linear layer `li` (among the linear ones) of a decode step."""
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    q, k, v = _linear_qkv(blk, h, pos, spec, dtype)
    hd = spec.head_dim
    lam = jnp.exp(-decay_slopes(spec.lin_heads))[None, :, None, None]
    state = lam * s_pool[li, slots] \
        + k.astype(_F32)[..., :, None] * v.astype(_F32)[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q.astype(_F32) * hd ** -0.5, state)
    s_pool = s_pool.at[li, slots].set(state)
    x = _gated_out(blk, x, h, o, spec, dtype)
    return _mlp(blk, x, spec, dtype), s_pool


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _decode_sparse(blk, x, li, pos, write_blk, write_off, tables, slots,
                   k_pool, v_pool, c_pool, *, spec, dtype):
    """Sparse layer `li` (among the sparse ones) of a decode step."""
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    q, k, v = _sparse_qkv(blk, h, spec, dtype)
    head = _head_layers(li, spec)
    at = (head[None, :], write_blk[:, None], write_off[:, None], 0)
    k_pool = k_pool.at[at].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[at].set(v.astype(v_pool.dtype))
    # the compressed key this token completes, if it does: entry m of the
    # row's slot, the mean over blocks m ... m + r - 1 of its table (a row
    # that completes none writes the scratch slot)
    r = spec.ck_kernel // spec.ck_stride
    m = jnp.maximum(pos + 1 - spec.ck_kernel, 0) // spec.ck_stride
    completes = ((pos + 1 - spec.ck_kernel) % spec.ck_stride == 0) \
        & (pos + 1 >= spec.ck_kernel)
    span = jnp.take_along_axis(tables, m[:, None] + jnp.arange(r), axis=1)
    ckey = jnp.sum(k_pool[head[None, :, None], span[:, None, :]]
                   .astype(_F32), axis=(2, 3))[:, :, 0] / spec.ck_kernel
    c_pool = c_pool.at[head[None, :], jnp.where(completes, slots, 0)[:, None],
                       m[:, None]].set(ckey.astype(c_pool.dtype))
    o = sparse_attend_rows(q, pos, tables, slots, li, k_pool, v_pool, c_pool,
                           spec=spec, dtype=dtype)
    x = _gated_out(blk, x, h, o, spec, dtype)
    return _mlp(blk, x, spec, dtype), k_pool, v_pool, c_pool


def hybrid_decode_step(params, cur, tables, pos, slots, k_pool, v_pool,
                       c_pool, s_pool, *, spec: LMSpec, dtype=jnp.float32):
    """One decode step for a bucketed batch. cur, pos, slots (B_b,)
    int32: each row's token, position and state slot (padding rows: the
    scratch slot and a table of the scratch block); tables (B_b,
    max_blocks) int32. Returns (logits (B_b, vocab) f32, k_pool, v_pool,
    c_pool, s_pool)."""
    b = cur.shape[0]
    bs = k_pool.shape[2]
    write_blk = tables[jnp.arange(b), pos // bs]
    write_off = pos % bs
    x = (params["embed"][cur][:, None, :] * spec.emb_scale).astype(dtype)
    for kind, li, blk in zip(spec.layer_kinds, layer_index(spec.layer_kinds),
                             params["blocks"]):
        if kind == LINEAR:
            x, s_pool = _decode_linear(blk, x, li, pos, slots, s_pool,
                                       spec=spec, dtype=dtype)
        else:
            x, k_pool, v_pool, c_pool = _decode_sparse(
                blk, x, li, pos, write_blk, write_off, tables, slots, k_pool,
                v_pool, c_pool, spec=spec, dtype=dtype)
    return (finish(params, x[:, 0], dtype, logit_div=spec.logit_div), k_pool,
            v_pool, c_pool, s_pool)


# -- chunk prefill ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _chunk_linear(blk, x, li, pos, live, fresh, slot, s_pool, *, spec,
                  dtype):
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    q, k, v = _linear_qkv(blk, h, pos, spec, dtype)
    state = jnp.where(fresh, 0.0, s_pool[li, slot])
    o, state = linear_scan(q, k, v, live, state, dtype)
    s_pool = s_pool.at[li, slot].set(state)
    x = _gated_out(blk, x, h, o, spec, dtype)
    return _mlp(blk, x, spec, dtype), s_pool


def _write_chunk(pool, head, blk_idx, blk_off, x, by_block: bool):
    """A chunk's keys (or values) x (C, G, hd), consecutive positions,
    into the pool layers `head` (G,): this family's own write, a KV head
    a pool layer, where `parts.write_chunk` writes one layer. `by_block`:
    the chunk starts on a block's first slot and is a whole number of
    blocks long, so each block of each head is written whole, in a loop
    of in-place updates (a scatter runs its C x G updates one after
    another). A block the prompt ends in takes its padding rows' values
    in the slots past the end, which are written again before any query
    may read them."""
    x = x.astype(pool.dtype)
    if not by_block:
        return pool.at[head[None, :], blk_idx[:, None], blk_off[:, None],
                       0].set(x)
    c, g, hd = x.shape
    bs = pool.shape[2]
    first = blk_idx.reshape(c // bs, bs)[:, 0]
    x = x.reshape(c // bs, bs, g, hd).transpose(0, 2, 1, 3) \
        .reshape(-1, 1, 1, bs, 1, hd)
    return jax.lax.fori_loop(0, x.shape[0], lambda i, p: (
        jax.lax.dynamic_update_slice(
            p, x[i], (head[i % g], first[i // g], 0, 0, 0))), pool)


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "by_block",
                                             "fused", "tile"))
def _chunk_sparse(blk, x, li, pos, last_pos, blk_idx, blk_off, tab, slot,
                  k_pool, v_pool, c_pool, *, spec, dtype, by_block, fused,
                  tile):
    c = x.shape[0]
    bs = k_pool.shape[2]
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    q, k, v = _sparse_qkv(blk, h, spec, dtype)
    head = _head_layers(li, spec)
    k_pool = _write_chunk(k_pool, head, blk_idx, blk_off, k, by_block)
    v_pool = _write_chunk(v_pool, head, blk_idx, blk_off, v, by_block)
    # every compressed key a token of this chunk completes: the means
    # over r blocks in a row, from r - 1 blocks before the chunk's first
    r = spec.ck_kernel // spec.ck_stride
    n_blk = -(-c // bs) + r
    first = jnp.maximum(pos[0] // bs - (r - 1), 0)
    span = jax.lax.dynamic_slice_in_dim(
        jnp.pad(tab, (0, n_blk)), first, n_blk)
    sums = jnp.sum(k_pool[head[:, None], span[None, :]].astype(_F32),
                   axis=2)[:, :, 0]                         # (G, n_blk, hd)
    n_key = n_blk - r + 1
    ckey = sum(sums[:, i:i + n_key] for i in range(r)) / spec.ck_kernel
    entry = first + jnp.arange(n_key)
    complete = spec.ck_stride * entry + spec.ck_kernel - 1 <= last_pos
    # the others go to the scratch slot, or past the last entry: dropped
    c_pool = c_pool.at[head[:, None], jnp.where(complete, slot, 0)[None, :],
                       entry[None, :]].set(ckey.astype(c_pool.dtype),
                                           mode="drop")
    ck = c_pool[head, slot].astype(dtype)                     # (G, MB, hd)
    n_q = min(_Q_TILE, c)

    # the scoring and the cut `n_q` queries at a time (their scores over
    # the compressed keys are (n_q, G, R, MB) float32)
    def select(xs):
        qt, pt = xs
        qg = qt.reshape(n_q, spec.n_kv, -1, qt.shape[-1])
        return attended_mask(_score_blocks(qg, pt, ck, spec), pt, spec)

    mask = jax.lax.map(select, (q.reshape((c // n_q, n_q) + q.shape[1:]),
                                pos.reshape(c // n_q, n_q)))
    o = sparse_attend_walk(
        q, pos, mask.reshape((c,) + mask.shape[2:]), tab,
        parts.tile_span(pos[0], c, tab.shape[0] * bs, tile)[1], li, k_pool,
        v_pool,         spec=spec, dtype=dtype, fused=fused, tile=tile)
    x = _gated_out(blk, x, h, o, spec, dtype)
    return _mlp(blk, x, spec, dtype), k_pool, v_pool, c_pool


def hybrid_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table, slot,
                         k_pool, v_pool, c_pool, s_pool, last_idx,
                         *, spec: LMSpec, dtype=jnp.float32,
                         by_block: bool = False, fused: bool = False,
                         tile: int = parts.CTX_TILE):
    """One prompt chunk of one sequence: the arguments of
    `paged_prefill_chunk` with the sequence's state slot after its table
    and the compressed-key and state pools after K and V. A chunk at
    ``pos0 == 0`` starts the sequence's states from zero. `by_block`
    (static): the caller vouches that `pos0` and the chunk's width are
    multiples of the block size (`_write_chunk`). `tile` (static): the
    context slots the sparse layers' walk covers an iteration; `fused`
    (static): it updates a tile in one kernel, and the caller asks
    `parts.fused_attend` whether it may. Returns (last real
    token's logits (vocab,) f32, k_pool, v_pool, c_pool, s_pool)."""
    c = ids.shape[1]
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    x = (params["embed"][ids[0]][:, None, :] * spec.emb_scale).astype(dtype)
    for kind, li, blk in zip(spec.layer_kinds, layer_index(spec.layer_kinds),
                             params["blocks"]):
        if kind == LINEAR:
            x, s_pool = _chunk_linear(blk, x, li, pos, live, pos0 == 0,
                                      slot, s_pool, spec=spec, dtype=dtype)
        else:
            x, k_pool, v_pool, c_pool = _chunk_sparse(
                blk, x, li, pos, pos0 + last_idx, blk_idx, blk_off, table,
                slot, k_pool, v_pool, c_pool, spec=spec, dtype=dtype,
                by_block=by_block, fused=fused, tile=tile)
    logits = finish(params, x[last_idx, 0][None, :], dtype,
                    logit_div=spec.logit_div)[0]
    return logits, k_pool, v_pool, c_pool, s_pool
