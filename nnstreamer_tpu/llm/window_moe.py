"""The decoder whose layers attend a window of the newest positions or
the whole context, over a paged pool a kind (pure jax, jitted by llm_exec
as ``jit_window_moe_decode_step`` and ``jit_window_moe_prefill_chunk``).

`LMSpec.layer_kinds` says which kind each layer is, WINDOW or FULL. What
is this family's own lives here: the layer's four norms and gated output,
rope on the window layers alone, and the decode walk over a work list
with a lower bound, grouped by KV head. From `llm/parts.py`: the
projections (`proj`), the norms (`norm`), the rope (`rope_rows`), the
head (`finish`), a layer's index among its kind (`layer_index`), a
chunk's writes (`write_chunk`), the decode step's work list (`walk_plan`,
`live_items`) and the chunk's walk over context tiles (`tile_span`,
`walk_tiles`, and `causal_update`: `pallas_ops.causal_block_update`, the
selected form's carry under a mask that positions alone decide, or the
plain update). From `llm/experts.py`: the MLP of a shared expert beside
the routed ones (`shared_mlp`).

The layer, for input x at position t, with four RMSNorms (`norm_eps`):
``h = x + N2(Attn(N1(x)))``, ``y = h + N4(MLP(N3(h)))``; the embedding is
multiplied by ``spec.emb_scale``; after the last layer a final norm and
the untied head.

- ``Attn(u)``: q (H x hd), k and v (Hkv x hd) from the fused ``wqkv``, no
  biases; per-head RMSNorm on q and k; **rope on WINDOW layers only**
  (base ``rope_theta``, half-split, all hd dims), none on FULL layers;
  scores ``q . k / sqrt(hd)`` in float32, causal, and on a WINDOW layer
  key s is seen by query t iff ``t - window < s <= t``;
  ``Attn = (softmax(scores) v * sigmoid(u Wg)) Wo``.
- ``MLP`` of the first ``dense_layers`` layers: a SwiGLU of
  ``dense_width``. Of the others: a shared SwiGLU of ``shared_width``
  every token passes, plus the routed experts' part: sigmoid scores over
  all ``n_experts``, the ``experts_per_tok`` of largest score + bias,
  weights ``route_scale * s_e / (sum of the chosen s + 1e-20)``, summed
  over the chosen experts *that are held here* (``experts_held`` from
  ``experts_first`` on: this chip's share of a layer that several chips
  divide). What the absent experts would have added is left out, and
  that partial result goes on to the next layer.

State: two pairs of K and V pools, ``(FULL layers, blocks, block_size,
Hkv, hd)`` and ``(WINDOW layers, window blocks, block_size, Hkv, hd)``,
each under an allocator and a table a sequence of its own
(`PagedKVCache`). Both tables are indexed by a position's block,
``t // block_size``; the window table's entries behind the window read the
scratch block, their blocks given back (`PagedKVCache.trim`), so a
sequence holds at most ``ceil(window / block_size) + 1`` window blocks
while it decodes, whatever its context.

How each program reads it.

- Decode (one token a row): the work list of live chunks is built twice
  a step, once a kind: a FULL layer's holds every chunk up to the row's
  position, a WINDOW layer's the chunks from ``max(0, pos - window + 1)``
  on. Every layer of a kind shares its list. An online softmax's carry
  a row and head merges T chunks an iteration, grouped by KV head (no
  key is repeated for the query heads that share it).
- Chunk prefill (C queries of one sequence): the context is walked a
  tile of `parts.CTX_TILE` slots at a time, a loop whose bounds come
  from ``pos0`` (`parts.tile_span`): all live tiles on a FULL layer, on a
  WINDOW layer the tiles from ``(pos0 - window + 1) // tile`` on (at most
  ``(window + C) / tile + 1`` of them whatever the context). A tile's
  mask is the causal edge and the window's; the update is the one
  `parts.fused_attend` chooses: plain XLA under the mask as an
  array, or the kernel, which is told the first query's position, the
  tile's first slot and the window and runs, a block of queries, the
  update with no mask (every query sees every slot), under a mask made
  from two iotas (the edges), or not at all (no query sees any slot).

Both return, beside the logits, ``(expert layers, experts_held + 1)``
int32: the tokens each held expert got and, last, the real tokens' pairs
routed to experts that are not held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.llm.experts import shared_mlp
from nnstreamer_tpu.llm.parts import (
    finish, layer_index, norm, proj, rope_rows, write_chunk)
from nnstreamer_tpu.llm.spec import WINDOW, LMSpec

_F32 = jnp.float32


def _qkv(blk, x, pos, kind: str, spec: LMSpec, dtype):
    """x (N, 1, D) at positions pos (N,): the normed input u (N, 1, D),
    q (N, H, hd), k and v (N, Hkv, hd); q and k normed a head, roped on a
    WINDOW layer."""
    n = x.shape[0]
    nh, nkv, hd = spec.n_heads, spec.n_kv, spec.head_dim
    u = norm(blk["ln1"], x, spec, dtype)
    qkv = proj(blk, "wqkv", u, dtype)
    qw, kw = nh * hd, nkv * hd
    q = qkv[..., :qw].reshape(n, 1, nh, hd)
    k = qkv[..., qw:qw + kw].reshape(n, 1, nkv, hd)
    v = qkv[..., qw + kw:].reshape(n, nkv, hd)
    q = norm(blk["q_norm"], q, spec, dtype)
    k = norm(blk["k_norm"], k, spec, dtype)
    if kind == WINDOW:
        q = rope_rows(q, pos, spec.rope_theta)
        k = rope_rows(k, pos, spec.rope_theta)
    return u, q[:, 0], k[:, 0], v


def _attn_out(blk, x, u, o, spec: LMSpec, dtype):
    """x + N2((o * sigmoid(u Wg)) Wo) for the attention's o (N, H * hd)."""
    o = o.reshape(x.shape[0], 1, -1).astype(dtype)
    o = o * jax.nn.sigmoid(proj(blk, "wg", u, dtype))
    return x + norm(blk["ln2"], proj(blk, "wo", o, dtype), spec, dtype)


def _mlp(blk, x, live, dense: bool, spec: LMSpec, dtype):
    """x + N4(MLP(N3(x))). Returns (x, the expert layer's counts with
    the pairs routed away last (experts_held + 1,) int32, or None for a
    dense layer)."""
    u = norm(blk["ln3"], x, spec, dtype)
    y, load = shared_mlp(blk, u, live, dense, spec, dtype)
    return x + norm(blk["ln4"], y, spec, dtype), load


def _layers(params, spec: LMSpec):
    """Each layer as (kind, its index among the layers of its kind: where
    its K and V live in that kind's pools, whether its MLP is dense, its
    parameters)."""
    kinds = spec.layer_kinds
    for i, (kind, li, blk) in enumerate(zip(kinds, layer_index(kinds),
                                            params["blocks"])):
        yield kind, li, i < spec.dense_layers, blk


def window_floor(pos, window: int):
    """The first position a WINDOW layer's query at `pos` attends."""
    return jnp.maximum(pos - (window - 1), 0)


# -- decode -------------------------------------------------------------------

def _attend_items(q, k_pool, v_pool, li, items, t):
    """Layer `li`'s attention of q (B, H, hd) over each row's work list
    `items` (`parts.live_items`; with a fifth value, the first
    live slot of each item's chunk): a loop over the list, T items at a
    time, the online-softmax carry (m, l, acc) a row, KV head and query
    head of its group in f32. Several items of one iteration may belong
    to one row; they merge through the (T, B) relation `own`. Returns
    (B, H * hd) f32."""
    row, blocks, last, n_iter, *first = items
    b, nh, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    grp = nh // nkv
    c = blocks.shape[1] * bs
    qg = q.reshape(b, nkv, grp, hd)
    slot = jnp.arange(c)
    hi = jax.lax.Precision.HIGHEST

    def body(j, state):
        m, l, acc = state
        r = jax.lax.dynamic_slice_in_dim(row, j * t, t)
        bl = jax.lax.dynamic_slice_in_dim(blocks, j * t, t)
        la = jax.lax.dynamic_slice_in_dim(last, j * t, t)
        kc = k_pool[li, bl].reshape(t, c, nkv, hd)
        vc = v_pool[li, bl].reshape(t, c, nkv, hd)
        s = jnp.einsum("tgrd,tcgd->tgrc", qg[r], kc,
                       preferred_element_type=_F32) * hd ** -0.5
        ok = slot[None, :] <= la[:, None]
        if first:
            fa = jax.lax.dynamic_slice_in_dim(first[0], j * t, t)
            ok = ok & (slot[None, :] >= fa[:, None])
        ok = ok[:, None, None, :]
        s = jnp.where(ok, s, -1e30)
        mi = jnp.max(s, axis=-1)                             # (T, Hkv, G)
        p = jnp.where(ok, jnp.exp(s - mi[..., None]), 0.0)
        ai = jnp.einsum("tgrc,tcgd->tgrd", p.astype(vc.dtype), vc,
                        preferred_element_type=_F32)
        own = (r[:, None] == jnp.arange(b)[None, :]) & (la >= 0)[:, None]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(own[:, :, None, None], mi[:, None], -1e30), axis=0))
        w = jnp.exp(mi - m_new[r])
        old = jnp.exp(m - m_new)
        ownf = own.astype(_F32)
        l = l * old + jnp.einsum(
            "tb,tgr->bgr", ownf, jnp.sum(p, axis=-1) * w, precision=hi)
        acc = acc * old[..., None] + jnp.einsum(
            "tb,tgrd->bgrd", ownf, ai * w[..., None], precision=hi)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (
        jnp.full((b, nkv, grp), -1e30, _F32),
        jnp.zeros((b, nkv, grp), _F32),
        jnp.zeros((b, nkv, grp, hd), _F32)))
    return (acc / l[..., None]).reshape(b, nh * hd)


@functools.partial(jax.jit, static_argnames=("kind", "dense", "t", "spec",
                                             "dtype"))
def _decode_layer(blk, x, li, pos, live, write_blk, write_off, items,
                  k_pool, v_pool, *, kind, dense, t, spec, dtype):
    """One layer of a decode step, `li` its index in its kind's pools
    (jitted with `li` an argument, so a step traces a layer of each
    shape once; XLA inlines the calls)."""
    u, q, k, v = _qkv(blk, x, pos, kind, spec, dtype)
    k_pool = k_pool.at[li, write_blk, write_off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[li, write_blk, write_off].set(v.astype(v_pool.dtype))
    o = _attend_items(q, k_pool, v_pool, li, items, t)
    x = _attn_out(blk, x, u, o, spec, dtype)
    x, load = _mlp(blk, x, live, dense, spec, dtype)
    return x, load, k_pool, v_pool


def window_moe_decode_step(params, cur, tables, wtables, pos, n_live,
                           k_pool, v_pool, wk_pool, wv_pool,
                           *, spec: LMSpec, dtype=jnp.float32):
    """One decode step for a bucketed batch. cur, pos (B_b,) int32;
    tables, wtables (B_b, max_blocks) int32: each row's table of the
    FULL layers' pools and of the WINDOW layers'; n_live () int32, the
    real rows (the first ones). Returns (logits (B_b, vocab) f32, the
    expert layers' counts (layers, experts_held + 1) int32, k_pool,
    v_pool, wk_pool, wv_pool)."""
    b = cur.shape[0]
    bs, nkv, hd = k_pool.shape[2], k_pool.shape[3], k_pool.shape[4]
    nb_c, n_chunks, t = parts.walk_plan(bs, nkv, hd, b, tables.shape[1])
    at = jnp.arange(b), pos // bs
    write_off = pos % bs
    live = jnp.arange(b) < n_live
    # one work list a kind, shared by every layer of the kind
    full = (tables[at], parts.live_items(tables, pos, bs, nb_c, n_chunks, t))
    win = (wtables[at], parts.live_items(
        wtables, pos, bs, nb_c, n_chunks, t,
        lo=window_floor(pos, spec.window)))
    x = (params["embed"][cur][:, None, :] * spec.emb_scale).astype(dtype)
    load = []
    for kind, li, dense, blk in _layers(params, spec):
        windowed = kind == WINDOW
        write_blk, items = win if windowed else full
        pools = (wk_pool, wv_pool) if windowed else (k_pool, v_pool)
        x, counts, *pools = _decode_layer(
            blk, x, li, pos, live, write_blk, write_off, items, *pools,
            kind=kind, dense=dense, t=t, spec=spec, dtype=dtype)
        if windowed:
            wk_pool, wv_pool = pools
        else:
            k_pool, v_pool = pools
        if counts is not None:
            load.append(counts)
    return (finish(params, x[:, 0], dtype, spec.norm_eps), jnp.stack(load),
            k_pool, v_pool, wk_pool, wv_pool)


# -- chunk prefill ------------------------------------------------------------

def attend_tiles(q, qpos, tab, span, li, k_pool, v_pool, *, window: int,
                 fused: bool, tile: int, dtype):
    """Layer `li`'s attention of a whole chunk: queries q (C, H, hd) at
    positions qpos (C,) of one sequence over the context tiles `span`
    (first, end; traced) of its table `tab` (MB,), a tile's K and V read
    once for all queries, under the causal edge and, where `window` > 0,
    the window's; qpos are consecutive positions, which the fused update
    makes its mask from. Returns (C, H * hd) f32."""
    c, nh, hd = q.shape
    bs, nkv = k_pool.shape[2], k_pool.shape[3]
    grp = nh // nkv
    tab = parts.whole_tiles(tab, tile, bs)
    qg = q.reshape(c, nkv, grp, hd)
    # the kernel's layout, a head's queries side by side: made once
    qh = qg.transpose(1, 2, 0, 3) if fused else None

    def read(bl):
        return (k_pool[li, bl].astype(dtype).reshape(tile, nkv, hd),
                v_pool[li, bl].astype(dtype).reshape(tile, nkv, hd))

    def update(j, kt, vt, state):
        return parts.causal_update(qg, qh, kt, vt, qpos, j * tile, state,
                                   window=window, fused=fused)

    # a padding query past the table's last tile attended nothing
    att = parts.walk_tiles(tab, span, tile // bs, read, update, (nkv, grp),
                           c, hd, l_floor=1e-30)
    return att.transpose(2, 0, 1, 3).reshape(c, nh * hd)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dense", "tile", "by_block", "fused", "spec", "dtype"))
def _chunk_layer(blk, x, li, pos, live, blk_idx, blk_off, tab, k_pool,
                 v_pool, *, kind, dense, tile, by_block, fused, spec, dtype):
    """One layer of a chunk: x (C, 1, D), the chunk's tokens as rows
    (jitted with `li` an argument, as `_decode_layer`)."""
    c = x.shape[0]
    u, q, k, v = _qkv(blk, x, pos, kind, spec, dtype)
    k_pool = write_chunk(k_pool, li, blk_idx, blk_off, k, by_block)
    v_pool = write_chunk(v_pool, li, blk_idx, blk_off, v, by_block)
    window = spec.window if kind == WINDOW else 0
    span = parts.tile_span(pos[0], c, tab.shape[0] * k_pool.shape[2], tile,
                           window)
    o = attend_tiles(q, pos, tab, span, li, k_pool, v_pool, window=window,
                     fused=fused, tile=tile, dtype=dtype)
    x = _attn_out(blk, x, u, o, spec, dtype)
    x, load = _mlp(blk, x, live, dense, spec, dtype)
    return x, load, k_pool, v_pool


def window_moe_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table,
                             wblk_idx, wtable, k_pool, v_pool, wk_pool,
                             wv_pool, last_idx, *, spec: LMSpec,
                             dtype=jnp.float32, by_block: bool = False,
                             fused: bool = False,
                             tile: int = parts.CTX_TILE):
    """One prompt chunk of one sequence: the arguments of
    `paged_prefill_chunk` with the WINDOW layers' write targets
    `wblk_idx` (C_b,) and table `wtable` (max_blocks,) after the FULL
    layers' and their pools after K and V. `by_block` (static): the
    caller vouches that `pos0` and the chunk's width are multiples of the
    block size (`parts.write_chunk`); `fused` (static): a walk updates a
    tile in one kernel, and the caller asks `parts.fused_attend` whether
    it may; `tile` (static): the context slots a walk covers an
    iteration. Returns (last real token's logits (vocab,)
    f32, the expert layers' counts over the chunk's real tokens (layers,
    experts_held + 1) int32, k_pool, v_pool, wk_pool, wv_pool)."""
    c = ids.shape[1]
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    x = (params["embed"][ids[0]][:, None, :] * spec.emb_scale).astype(dtype)
    load = []
    for kind, li, dense, blk in _layers(params, spec):
        windowed = kind == WINDOW
        where = (wblk_idx, blk_off, wtable) if windowed \
            else (blk_idx, blk_off, table)
        pools = (wk_pool, wv_pool) if windowed else (k_pool, v_pool)
        x, counts, *pools = _chunk_layer(
            blk, x, li, pos, live, *where, *pools, kind=kind, dense=dense,
            tile=tile, by_block=by_block, fused=fused, spec=spec,
            dtype=dtype)
        if windowed:
            wk_pool, wv_pool = pools
        else:
            k_pool, v_pool = pools
        if counts is not None:
            load.append(counts)
    logits = finish(params, x[last_idx, 0][None, :], dtype,
                    spec.norm_eps)[0]
    return (logits, jnp.stack(load), k_pool, v_pool, wk_pool, wv_pool)
