"""The decoder whose layers attend a window of the newest positions or
the whole context, over a paged pool a kind (pure jax, jitted by llm_exec
as ``jit_window_moe_decode_step`` and ``jit_window_moe_prefill_chunk``).

`LMSpec.layer_kinds` says which kind each layer is, WINDOW or FULL. The
projections (`_proj`), the norms (`rmsnorm`), the rope (`_rope_rows`) and
the dense SwiGLU (`_mlp_paged`) are the dense family's functions, the
expert layer (`sparse_moe._expert_layer`), the choice of the chunk's tile
update (`sparse_moe.fused_attend`) and the plain one (`attend_plain`) the
sparse-expert family's, and the decode step's work list
(`paged_model._live_items`) the dense family's with a lower bound. The
fused tile update is this family's and the latent one's:
`pallas_ops.causal_block_update`, the selected form's carry under a mask
that positions alone decide.

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
  tile of `sparse_moe._CTX_TILE` slots at a time, a loop whose bounds
  come from ``pos0``: all live tiles on a FULL layer, on a WINDOW layer
  the tiles from ``(pos0 - window + 1) // tile`` on (at most
  ``(window + C) / tile + 1`` of them whatever the context). A tile's
  mask is the causal edge and the window's; the update is the one
  `sparse_moe.fused_attend` chooses: plain XLA under the mask as an
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

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm import sparse_moe
from nnstreamer_tpu.llm.paged_model import (
    _live_items, _mlp_paged, _proj, _rope_rows, _walk_plan)
from nnstreamer_tpu.llm.spec import WINDOW, LMSpec
from nnstreamer_tpu.models.transformer import rmsnorm

_F32 = jnp.float32


def _norm(blk, name, x, spec: LMSpec, dtype):
    return rmsnorm(x, blk[name].astype(dtype), spec.norm_eps)


def _qkv(blk, x, pos, kind: str, spec: LMSpec, dtype):
    """x (N, 1, D) at positions pos (N,): the normed input u (N, 1, D),
    q (N, H, hd), k and v (N, Hkv, hd); q and k normed a head, roped on a
    WINDOW layer."""
    n = x.shape[0]
    nh, nkv, hd = spec.n_heads, spec.n_kv, spec.head_dim
    u = _norm(blk, "ln1", x, spec, dtype)
    qkv = _proj(blk, "wqkv", u, dtype)
    qw, kw = nh * hd, nkv * hd
    q = qkv[..., :qw].reshape(n, 1, nh, hd)
    k = qkv[..., qw:qw + kw].reshape(n, 1, nkv, hd)
    v = qkv[..., qw + kw:].reshape(n, nkv, hd)
    q = _norm(blk, "q_norm", q, spec, dtype)
    k = _norm(blk, "k_norm", k, spec, dtype)
    if kind == WINDOW:
        q = _rope_rows(q, pos, spec.rope_theta)
        k = _rope_rows(k, pos, spec.rope_theta)
    return u, q[:, 0], k[:, 0], v


def _attn_out(blk, x, u, o, spec: LMSpec, dtype):
    """x + N2((o * sigmoid(u Wg)) Wo) for the attention's o (N, H * hd)."""
    o = o.reshape(x.shape[0], 1, -1).astype(dtype)
    o = o * jax.nn.sigmoid(_proj(blk, "wg", u, dtype))
    return x + _norm(blk, "ln2", _proj(blk, "wo", o, dtype), spec, dtype)


def _mlp(blk, x, live, dense: bool, spec: LMSpec, dtype):
    """x + N4(MLP(N3(x))). Returns (x, the expert layer's counts with
    the pairs routed away last (experts_held + 1,) int32, or None for a
    dense layer)."""
    u = _norm(blk, "ln3", x, spec, dtype)
    if dense:
        y, load = _mlp_paged(blk, u, dtype), None
    else:
        y, counts, away = sparse_moe._expert_layer(blk, u[:, 0], live, spec,
                                                   dtype)
        # the shared expert through the dense family's products
        shared = _mlp_paged({"wi": blk["swi"], "wd": blk["swd"]}, u, dtype)
        y = shared + y[:, None, :]
        load = jnp.concatenate([counts, away[None]])
    return x + _norm(blk, "ln4", y, spec, dtype), load


def _finish(params, x, spec: LMSpec, dtype):
    x = rmsnorm(x, params["ln_f"].astype(dtype), spec.norm_eps)
    return _proj(params, "head", x, dtype).astype(_F32)


def _layers(params, spec: LMSpec):
    """Each layer as (kind, its index among the layers of its kind: where
    its K and V live in that kind's pools, whether its MLP is dense, its
    parameters)."""
    seen = {}
    for i, (kind, blk) in enumerate(zip(spec.layer_kinds, params["blocks"])):
        li = seen.get(kind, 0)
        seen[kind] = li + 1
        yield kind, li, i < spec.dense_layers, blk


def window_floor(pos, window: int):
    """The first position a WINDOW layer's query at `pos` attends."""
    return jnp.maximum(pos - (window - 1), 0)


# -- decode -------------------------------------------------------------------

def _attend_items(q, k_pool, v_pool, li, items, t):
    """Layer `li`'s attention of q (B, H, hd) over each row's work list
    `items` (`paged_model._live_items`; with a fifth value, the first
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
    nb_c, n_chunks, t = _walk_plan(bs, nkv, hd, b, tables.shape[1])
    at = jnp.arange(b), pos // bs
    write_off = pos % bs
    live = jnp.arange(b) < n_live
    # one work list a kind, shared by every layer of the kind
    full = (tables[at], _live_items(tables, pos, bs, nb_c, n_chunks, t))
    win = (wtables[at], _live_items(
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
    return (_finish(params, x[:, 0], spec, dtype), jnp.stack(load),
            k_pool, v_pool, wk_pool, wv_pool)


# -- chunk prefill ------------------------------------------------------------

def tile_span(pos0, c: int, slots: int, tile: int, window: int = 0):
    """(first, end) of the context tiles of `tile` slots a chunk of `c`
    queries at `pos0` walks under a table of `slots` slots: up to the
    chunk's own last tile, and on a WINDOW layer (`window` > 0) from the
    tile that holds the first query's window floor. In arithmetic that
    the host's ints and the program's traced `pos0` both take."""
    n, cap = -(-(pos0 + c) // tile), -(-slots // tile)
    end = n - (n > cap) * (n - cap)
    if not window:
        return 0 * end, end
    lo = pos0 - (window - 1)
    return (lo > 0) * (lo // tile), end


def _write_chunk(pool, li, blk_idx, blk_off, x, by_block: bool):
    """A chunk's keys (or values) x (C, Hkv, hd), consecutive positions,
    into layer `li` of `pool`; `by_block` as
    `sparse_moe._write_chunk`: each block written whole."""
    if not by_block:
        return pool.at[li, blk_idx, blk_off].set(x.astype(pool.dtype))
    bs = pool.shape[2]
    first = blk_idx.reshape(x.shape[0] // bs, bs)[:, 0]
    return sparse_moe._put_blocks(pool, li, first, x)


def attend_tile_plain(qg, kt, vt, qpos, first, window: int, state):
    """One context tile in plain XLA (`sparse_moe.attend_plain`) under
    the causal edge and, where `window` > 0, the window's: the queries at
    positions qpos (C,) against the slots from `first` on, the mask as
    selection keys of 1 and 0 under a threshold of 0 with no tie taken."""
    c = qpos.shape[0]
    s = (first + jnp.arange(kt.shape[0]))[None, :]
    on = s <= qpos[:, None]
    if window:
        on = on & (s > qpos[:, None] - window)
    return sparse_moe.attend_plain(
        qg, kt, vt, on.astype(jnp.uint32), jnp.zeros((c,), jnp.uint32),
        jnp.full((c,), -1, jnp.int32), 0, state)


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
    if tile % bs:
        raise ValueError(f"block_size {bs} does not divide the context "
                         f"tile of {tile} slots")
    nb_t = tile // bs
    max_tiles = -(-tab.shape[0] // nb_t)
    # the table's tail past max_blocks reads block 0: the scratch block
    tab = jnp.pad(tab, (0, max_tiles * nb_t - tab.shape[0]))
    qg = q.reshape(c, nkv, grp, hd)
    # the kernel's layout, a head's queries side by side: made once
    qh = qg.transpose(1, 2, 0, 3) if fused else None

    def attend_tile(j, state):
        bl = jax.lax.dynamic_slice_in_dim(tab, j * nb_t, nb_t)
        kt = k_pool[li, bl].astype(dtype).reshape(tile, nkv, hd)
        vt = v_pool[li, bl].astype(dtype).reshape(tile, nkv, hd)
        if fused:
            # the mask from the positions, inside the kernel
            return pallas_ops.causal_block_update(
                qh, kt, vt, qpos[0], j * tile, *state, window=window)
        return attend_tile_plain(qg, kt, vt, qpos, j * tile, window, state)

    _, l, acc = jax.lax.fori_loop(*span, attend_tile, (
        jnp.full((nkv, grp, c), -1e30, _F32),
        jnp.zeros((nkv, grp, c), _F32),
        jnp.zeros((nkv, grp, c, hd), _F32)))
    # a padding query past the table's last tile attended nothing
    att = acc / jnp.maximum(l, 1e-30)[..., None]
    return att.transpose(2, 0, 1, 3).reshape(c, nh * hd)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dense", "tile", "by_block", "fused", "spec", "dtype"))
def _chunk_layer(blk, x, li, pos, live, blk_idx, blk_off, tab, k_pool,
                 v_pool, *, kind, dense, tile, by_block, fused, spec, dtype):
    """One layer of a chunk: x (C, 1, D), the chunk's tokens as rows
    (jitted with `li` an argument, as `_decode_layer`)."""
    c = x.shape[0]
    u, q, k, v = _qkv(blk, x, pos, kind, spec, dtype)
    k_pool = _write_chunk(k_pool, li, blk_idx, blk_off, k, by_block)
    v_pool = _write_chunk(v_pool, li, blk_idx, blk_off, v, by_block)
    window = spec.window if kind == WINDOW else 0
    span = tile_span(pos[0], c, tab.shape[0] * k_pool.shape[2], tile, window)
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
                             tile: int = sparse_moe._CTX_TILE):
    """One prompt chunk of one sequence: the arguments of
    `paged_prefill_chunk` with the WINDOW layers' write targets
    `wblk_idx` (C_b,) and table `wtable` (max_blocks,) after the FULL
    layers' and their pools after K and V. `by_block`, `fused` (static)
    as `sparse_moe_prefill_chunk`; `tile` (static): the context slots a
    walk covers an iteration. Returns (last real token's logits (vocab,)
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
    logits = _finish(params, x[last_idx, 0][None, :], spec, dtype)[0]
    return (logits, jnp.stack(load), k_pool, v_pool, wk_pool, wv_pool)
