"""The sparse-expert decoder whose attention a learned indexer chooses
(pure jax, jitted by llm_exec as ``jit_sparse_moe_decode_step`` and
``jit_sparse_moe_prefill_chunk``).

What is this family's own lives here: the indexer, the selection of a
decode row's slots, attention over the selected slots, and the two entry
points. From `llm/parts.py`: the projections (`proj`), the rope
(`rope_rows`, with the model's own base), the head (`finish`), a chunk's
writes (`write_chunk`), the exact selection of a chunk's slots
(`sort_keys`, `select_cut`) and the walk over context tiles (`walk_tiles`
around this family's own read and update, `attend_plain` the plain one).
From `llm/experts.py`: the expert layer.

The layer, for input x at position t (`LMSpec` gives the sizes):

1. ``h = rmsnorm(x; ln1)``; q, k, v from the fused ``wqkv``
   (H x hd | Hkv x hd | Hkv x hd: the query width need not be the
   hidden size); per-head RMSNorm on q and k (``q_norm``, ``k_norm``);
   rope on all hd dims, base ``spec.rope_theta``.
2. Indexer, from the fused ``widx`` (Hi x di | di | Hi): qI, kI (one
   key head) roped with the same base, w. Score
   ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for s <= t, f32.
   S_t = the min(topk, t + 1) positions of largest score, ties to the
   lower position: exact, never `approx_max_k`.
3. Softmax attention over S_t only, f32 scores, one S_t for all heads.
4. ``g = rmsnorm(x; ln2)``; router logits and their softmax in f32; the
   `experts_per_tok` largest, renormalised; every token reaches all of
   its experts, whatever the load (no capacity, nothing dropped).

State: K and V pools as the dense family's, and a third pool of indexer
keys in the same blocks under the same tables (PagedKVCache.idx):
``(L, num_blocks, block_size // pack, pack * di)``, `pack` neighbouring
slots side by side in a row of up to 128 values. All three hold the
compute type's values in the compute type: a read's ``astype(dtype)`` is
the identity. (Under a wider pool it is a cast in front of the matrix
unit, which XLA:TPU moves ahead of the gather and out of the loop over
context tiles, converting the whole pool once a layer: compiled text,
PR 27 and PR 30. The step functions take such pools, for the tests.)

How each program reads it.

- Decode (one token a row): the row's indexer keys are gathered through
  its whole table behind the position mask (a slot of the indexer pool
  is a sixteenth of its K+V at the published sizes), `jax.lax.top_k`
  picks the slots (ties to the lower position is its rule), and K and V
  are gathered through the table at slot granularity: `topk` slots a
  row, not the context. A row with ``pos < topk`` attends every live slot.
- Chunk prefill (C queries of one sequence): per-query gathers of
  `topk` slots would move more bytes than the context holds, so the
  chunk walks the context written so far in tiles of `CTX_TILE` slots,
  a loop whose trip count comes from ``pos0`` (one program whatever the
  prompt's length): index scores of each tile are kept as order-
  preserving integer keys ``(C, S)``, the k-th largest key of each query
  is found exactly by a search over its 32 bits (ties cut at the lower
  positions by a second search, run only when a tie straddles the cut),
  and attention walks the same tiles with an online softmax under the
  mask ``key > T or (key == T and position <= P)``. No
  ``(heads, chunk, max_len)`` score tensor exists. How a tile updates
  the softmax's carry is chosen by `parts.fused_attend` from the backend and
  the shapes: on a TPU, where the head width is a whole lane tile, one
  kernel a tile (`pallas_ops.selected_block_update`: scores, mask,
  running maximum and sum, exponentials and the value product of a
  block of queries stay in fast memory, and nothing of shape
  ``(heads, C, CTX_TILE)`` is written); elsewhere `attend_plain`, whose
  tile of float32 scores passes through memory three times. The two
  attend the same slots and differ in the order float32 sums are added
  inside a tile. XLA still reads the pool through the table for both:
  ``paged_kernel`` stays ``xla``.
- Expert layer (both): `experts.expert_layer`, which says how the
  grouped products are chosen. It returns the tokens each expert got,
  which rides the step's read-back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.llm.experts import expert_layer
from nnstreamer_tpu.llm.parts import (
    finish, idx_write, proj, rope_rows, select_cut, sort_keys, write_chunk)
from nnstreamer_tpu.llm.spec import LMSpec
from nnstreamer_tpu.models.transformer import rmsnorm

_F32 = jnp.float32
_U32 = jnp.uint32


def _project(blk, x, pos, spec: LMSpec, dtype):
    """x (N, 1, D) at positions pos (N,): q (N, H, hd), k and v
    (N, Hkv, hd), the indexer's qI (N, Hi, di), kI (N, di), w (N, Hi)
    f32. Rows are independent: a decode batch and a chunk's tokens take
    the same path."""
    n = x.shape[0]
    nh, nkv, hd = spec.n_heads, spec.n_kv, spec.head_dim
    hi, di = spec.idx_heads, spec.idx_dim
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    qkv = proj(blk, "wqkv", h, dtype)
    qw, kw = nh * hd, nkv * hd
    q = qkv[..., :qw].reshape(n, 1, nh, hd)
    k = qkv[..., qw:qw + kw].reshape(n, 1, nkv, hd)
    v = qkv[..., qw + kw:].reshape(n, 1, nkv, hd)
    if spec.qk_norm:
        q = rmsnorm(q, blk["q_norm"].astype(dtype))
        k = rmsnorm(k, blk["k_norm"].astype(dtype))
    q = rope_rows(q, pos, spec.rope_theta)
    k = rope_rows(k, pos, spec.rope_theta)
    idx = proj(blk, "widx", h, dtype)
    qi = rope_rows(idx[..., :hi * di].reshape(n, 1, hi, di), pos,
                    spec.rope_theta)
    ki = rope_rows(idx[..., hi * di:hi * di + di].reshape(n, 1, 1, di),
                    pos, spec.rope_theta)
    w = idx[..., hi * di + di:].reshape(n, hi).astype(_F32)
    return q[:, 0], k[:, 0], v[:, 0], qi[:, 0], ki[:, 0, 0], w


def _idx_scores(qi, w, rows, di, shared: bool):
    """Index scores of queries qi (N, Hi, di), w (N, Hi) f32 against the
    gathered pool rows `rows` (..., R, pack * di), each holding `pack`
    slots: per query its own rows (N, R, .) or, `shared`, one (R, .) for
    all, in the compute type as qi is. Returns (N, R * pack) f32 in slot
    order; the products accumulate in f32."""
    pack = rows.shape[-1] // di
    per = []
    for i in range(pack):
        k = rows[..., i * di:(i + 1) * di]
        if shared:
            s = jnp.einsum("njd,rd->jnr", qi, k,
                           preferred_element_type=_F32)
            per.append(jnp.sum(w.T[:, :, None] * jax.nn.relu(s), axis=0))
        else:
            s = jnp.einsum("njd,nrd->njr", qi, k,
                           preferred_element_type=_F32)
            per.append(jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1))
    return jnp.stack(per, axis=-1).reshape(qi.shape[0], -1)


# -- decode -------------------------------------------------------------------

def select_rows(scores, pos, topk):
    """Exact selection for a decode batch: scores (B, S) f32 over each
    row's table, pos (B,). Returns (slots (B, K) int32, valid (B, K)):
    the min(topk, pos + 1) live slots of largest score, ties to the
    lower slot (`jax.lax.top_k`'s rule)."""
    s = scores.shape[1]
    k = min(int(topk), s)
    live = jnp.arange(s)[None, :] <= pos[:, None]
    # -0.0 and +0.0 tie (a sort may order them): one zero
    scores = jnp.where(scores == 0.0, 0.0, scores)
    _, sel = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), k)
    valid = jnp.arange(k)[None, :] < jnp.minimum(k, pos + 1)[:, None]
    return sel, valid


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _decode_layer(blk, x, li, pos, live, write_blk, write_off, tables,
                  k_pool, v_pool, i_pool, *, spec, dtype):
    """Layer `li` of a decode step (jitted with `li` an argument, so a
    step traces one layer; XLA inlines the calls)."""
    b = x.shape[0]
    nkv, hd = spec.n_kv, spec.head_dim
    grp = spec.n_heads // nkv
    bs = k_pool.shape[2]
    q, k, v, qi, ki, w = _project(blk, x, pos, spec, dtype)
    k_pool = k_pool.at[li, write_blk, write_off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[li, write_blk, write_off].set(v.astype(v_pool.dtype))
    i_pool = idx_write(i_pool, li, write_blk, write_off, ki)
    rows = i_pool[li, tables].astype(dtype)      # (B, MB, bs/pack, pack*di)
    scores = _idx_scores(qi, w, rows.reshape(b, -1, rows.shape[-1]),
                         spec.idx_dim, shared=False)            # (B, S)
    sel, valid = select_rows(scores, pos, spec.topk)
    blk_of = jnp.take_along_axis(tables, sel // bs, axis=1)     # (B, K)
    kc = k_pool[li, blk_of, sel % bs].astype(dtype)       # (B, K, Hkv, hd)
    vc = v_pool[li, blk_of, sel % bs].astype(dtype)
    qg = q.reshape(b, nkv, grp, hd)
    sc = jnp.einsum("bgrd,bkgd->bgrk", qg, kc,
                    preferred_element_type=_F32) * hd ** -0.5
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0).astype(dtype)
    att = jnp.einsum("bgrk,bkgd->bgrd", p, vc,
                     preferred_element_type=_F32).astype(dtype)
    x = x + proj(blk, "wo", att.reshape(b, 1, -1), dtype)
    g = rmsnorm(x, blk["ln2"].astype(dtype))
    y, counts, _ = expert_layer(blk, g[:, 0], live, spec, dtype)
    return x + y[:, None, :], counts, k_pool, v_pool, i_pool


def sparse_moe_decode_step(params, cur, tables, pos, n_live, k_pool, v_pool,
                           i_pool, *, spec: LMSpec, dtype=jnp.float32):
    """One decode step for a bucketed batch. cur, pos (B_b,) int32;
    tables (B_b, max_blocks) int32; n_live () int32, the real rows (the
    first ones). Returns (logits (B_b, vocab) f32, tokens an expert
    (L, E) int32, k_pool, v_pool, i_pool)."""
    b = cur.shape[0]
    bs = k_pool.shape[2]
    write_blk = tables[jnp.arange(b), pos // bs]
    write_off = pos % bs
    live = jnp.arange(b) < n_live
    x = params["embed"][cur][:, None, :].astype(dtype)
    load = []
    for li, blk in enumerate(params["blocks"]):
        x, counts, k_pool, v_pool, i_pool = _decode_layer(
            blk, x, li, pos, live, write_blk, write_off, tables,
            k_pool, v_pool, i_pool, spec=spec, dtype=dtype)
        load.append(counts)
    return (finish(params, x[:, 0], dtype), jnp.stack(load),
            k_pool, v_pool, i_pool)


# -- chunk prefill ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("tile", "by_block", "fused",
                                             "spec", "dtype"))
def _chunk_layer(blk, x, li, pos, live, blk_idx, blk_off, tab, n_tiles,
                 k_pool, v_pool, i_pool, *, tile, by_block, fused,
                 spec: LMSpec, dtype):
    """Layer `li` of a chunk: x (C, 1, D), the chunk's tokens as rows
    (jitted with `li` an argument, as `_decode_layer`)."""
    c = x.shape[0]
    nkv, hd, nh = spec.n_kv, spec.head_dim, spec.n_heads
    grp = nh // nkv
    bs = k_pool.shape[2]
    nb_t = tile // bs
    s_pad = tab.shape[0] * bs
    q, k, v, qi, ki, w = _project(blk, x, pos, spec, dtype)
    k_pool = write_chunk(k_pool, li, blk_idx, blk_off, k, by_block)
    v_pool = write_chunk(v_pool, li, blk_idx, blk_off, v, by_block)
    i_pool = write_chunk(i_pool, li, blk_idx, blk_off, ki, by_block)
    slot = jnp.arange(tile)

    def score_tile(j, keys):
        bl = jax.lax.dynamic_slice_in_dim(tab, j * nb_t, nb_t)
        rows = i_pool[li, bl].astype(dtype)
        sc = _idx_scores(qi, w, rows.reshape(-1, rows.shape[-1]),
                         spec.idx_dim, shared=True)             # (C, tile)
        may = (j * tile + slot)[None, :] <= pos[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            keys, jnp.where(may, sort_keys(sc), _U32(0)), j * tile, 1)

    keys = jax.lax.fori_loop(0, n_tiles, score_tile,
                             jnp.zeros((c, s_pad), _U32))
    k_eff = jnp.minimum(min(int(spec.topk), s_pad), pos + 1)
    t, cut = select_cut(keys, n_tiles, tile, k_eff)
    qg = q.reshape(c, nkv, grp, hd)
    # the kernel's layout, a head's queries side by side: made once
    qh = qg.transpose(1, 2, 0, 3) if fused else None

    def read(bl):
        return (k_pool[li, bl].astype(dtype).reshape(tile, nkv, hd),
                v_pool[li, bl].astype(dtype).reshape(tile, nkv, hd))

    def update(j, kt, vt, state):
        if fused:
            return pallas_ops.selected_block_update(
                qh, kt, vt, keys, t, cut, j, *state,
                block_q=parts.FUSED_Q_BLOCK)
        key_t = jax.lax.dynamic_slice_in_dim(keys, j * tile, tile, 1)
        return parts.attend_plain(qg, kt, vt, key_t, t, cut, j * tile, state)

    att = parts.walk_tiles(tab, (0, n_tiles), nb_t, read, update,
                           (nkv, grp), c, hd)
    att = att.transpose(2, 0, 1, 3).astype(dtype)
    x = x + proj(blk, "wo", att.reshape(c, 1, -1), dtype)
    g = rmsnorm(x, blk["ln2"].astype(dtype))
    y, counts, _ = expert_layer(blk, g[:, 0], live, spec, dtype)
    return x + y[:, None, :], counts, k_pool, v_pool, i_pool


def sparse_moe_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table,
                             k_pool, v_pool, i_pool, last_idx,
                             *, spec: LMSpec, dtype=jnp.float32,
                             by_block: bool = False, fused: bool = False):
    """One prompt chunk of one sequence; the arguments of
    `paged_prefill_chunk` with the indexer's pool after K and V.
    `by_block` (static): the caller vouches that `pos0` and the chunk's
    width are multiples of the block size (`parts.write_chunk`). `fused`
    (static): the attention walk updates a tile in one kernel; the
    caller asks `parts.fused_attend` whether it may.
    Returns (last real token's logits (vocab,) f32, tokens an expert
    (L, E) int32 over the chunk's real tokens, k_pool, v_pool, i_pool).
    """
    c = ids.shape[1]
    bs = k_pool.shape[2]
    tile = parts.CTX_TILE
    # padded here and not a layer: the layer's three walks share it
    tab = parts.whole_tiles(table, tile, bs)
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    _, n_tiles = parts.tile_span(pos0, c, table.shape[0] * bs, tile)
    x = params["embed"][ids[0]][:, None, :].astype(dtype)     # (C, 1, D)
    load = []
    for li, blk in enumerate(params["blocks"]):
        x, counts, k_pool, v_pool, i_pool = _chunk_layer(
            blk, x, li, pos, live, blk_idx, blk_off, tab, n_tiles,
            k_pool, v_pool, i_pool, tile=tile, by_block=by_block,
            fused=fused, spec=spec, dtype=dtype)
        load.append(counts)
    logits = finish(params, x[last_idx, 0][None, :], dtype)[0]
    return logits, jnp.stack(load), k_pool, v_pool, i_pool
