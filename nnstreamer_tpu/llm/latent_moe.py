"""The decoder whose attention is latent: a token keeps one compressed
row and one roped key, all heads share both, and two programs read that
one cache in two forms (pure jax, jitted by llm_exec as
``jit_latent_moe_decode_step`` and ``jit_latent_moe_prefill_chunk``).

What is this family's own lives here: the projections through the two
ranks, YaRN's rope, the two forms of the attention, the rule that picks
one, and the decode walk in the latent (the plain one over a work list,
the fused one's kernel `pallas_paged.latent_decode_attn`). From
`llm/parts.py`: the norms (`norm`), the products (`proj`), the head
(`finish`), a chunk's writes into both pools (`write_chunk`), the plain
decode walk's work list (`live_items`) and the chunk's walk over context
tiles (`tile_span`, `walk_tiles` around this family's own read of a tile
in either form, and `causal_update`: `pallas_ops.causal_block_update`,
whose mask the kernel makes from positions, or the plain update). From
`llm/experts.py`: the MLP of shared experts beside the routed ones
(`shared_mlp`), whose router chooses inside groups under this family's
spec.

The layer, for input x at position t, two RMSNorms (`norm_eps`):
``h = x + Attn(N1(x))``, ``y = h + MLP(N2(h))``; after the last layer a
final norm and the untied head. No biases.

- ``Attn(u)``: ``cq = RMSNorm(u Wqa)`` (`q_rank`); ``q = cq Wqb``: H heads
  of ``nope_dim + rope_dim`` = (q_nope | q_pe). ``u Wkva`` (`kv_rank` +
  `rope_dim`) = (c | k_pe): ``c = RMSNorm(c)``, the latent; k_pe is one key
  for all heads and is not normed. ``c Wkvb``: H heads of ``nope_dim +
  v_dim`` = (k_nope | v). Rope on q_pe and k_pe: the pairs (2i, 2i + 1)
  turned by ``t * f_i`` (`yarn_freqs`), written back as (the pairs' first
  values | their second values), the same for q and k, so the dot product
  is the interleaved one's. Scores in float32,
  ``(q_nope . k_nope + q_pe . k_pe) * score_scale``, causal, softmax in
  float32; the heads' sums of v, side by side, through ``Wo``.
- ``MLP`` of the first `dense_layers` layers: a SwiGLU of `dense_width`.
  Of the others: a shared SwiGLU of `shared_width` every token passes,
  unweighted, plus the routed experts' part (`experts.route`: softmax
  over all `n_experts`, `topk_group` of `n_group` groups by their best
  expert, `experts_per_tok` among those, weights ``route_scale * s_e`` and
  not renormalised), summed over the chosen experts held here.

State: two pools under one table a sequence (`PagedKVCache` with
``values=False``): the latents ``(layers, blocks, block_size, 1,
kv_rank)`` and the roped keys ``(layers, blocks, block_size // pack, pack
* rope_dim)``, `pack` neighbouring tokens side by side in a row of 128
values (`paged_cache.idx_pack`, as the sparse-expert family's indexer
keys). At the published widths 512 + 64 values a token a layer, 1,152
bytes in bfloat16, both minor dimensions whole lane tiles, and no V pool:
the values are a product of the latent.

The two forms, over the same cache.

- *Absorbed* (`absorbed_queries`): ``Wkvb`` a head is (W_UK | W_UV);
  ``q_nope . k_nope = (q_nope W_UK^T) . c`` and a head's output is
  ``(sum_s p_s c_s) W_UV``, so a query attends in the latent: 2 H (2
  kv_rank + rope_dim) operations a (query, key), nothing expanded, every
  head reading the same row. The decode step always (one query a row),
  and a chunk of few queries. The step walks a row's context in one of
  two ways, which `fused_decode` picks from the backend and the pools'
  widths alone: one kernel a layer whose programs are the rows, a row's
  carry kept on the chip and the pools read in place through the table;
  or a work list of live chunks as the dense family's, the items' sums
  merged into every row's carry an iteration (`attend_latent`).
- *Expanded* (`attend_tiles` with ``expanded``): a context tile's latents
  go through ``Wkvb`` once for all the chunk's queries, 2 kv_rank H
  (nope_dim + v_dim) a key, and a pair then costs 2 H (nope_dim + rope_dim
  + v_dim). A chunk of many queries.

`expanded_attend` picks from the static bucket alone, by the operations:
at the published widths the forms cross at 171 queries a key. How a tile
updates the softmax's carry is `parts.fused_attend`'s to say (the
expanded form only: a head there has a K and a V of its own, which is the
kernel's layout; its K is filled with zeros to a whole lane tile, and a
head being a group of one, a program of the kernel takes 1,024 queries).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.backends import pallas_paged
from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.llm.experts import shared_mlp
from nnstreamer_tpu.llm.paged_cache import idx_pack
from nnstreamer_tpu.llm.parts import finish, idx_write, norm, proj, write_chunk
from nnstreamer_tpu.llm.spec import LMSpec

_F32 = jnp.float32

# The plain decode walk's extents: slots a chunk of the work list holds
# (whole blocks) and chunks an iteration gathers and attends. All heads read
# one row a slot, so a chunk is a (heads, kv_rank + rope_dim) x (slots)
# product and wants many slots; a row wastes half a chunk of masked slots.
DECODE_CHUNK = 512
DECODE_ITEMS = 16
# Slots a step of the fused walk's kernel copies and attends (whole blocks).
# One layer alone on the v5e, 31 rows of a bucket of 32 at 6.5 k positions
# each (PERF.md, PR 43): 0.97 ms at 256, 0.77 at 512, 0.66 at 1,024, 0.67 at
# 2,048 (the plain walk 1.36): a step's fixed cost outweighs the half step a
# row wastes, up to 1,024.
DECODE_STEP = 1024


# -- YaRN -----------------------------------------------------------------------

def _mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(spec: LMSpec) -> np.ndarray:
    """The angle a position of pair i of the roped dims: ``e_i =
    theta^(-2i / rope_dim)``, divided by `yarn_factor` past the ramp
    between the pairs that make `yarn_beta_fast` and `yarn_beta_slow`
    turns over `yarn_orig_len` positions. (rope_dim / 2,) float32."""
    dim, theta = spec.rope_dim, float(spec.rope_theta)
    e = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not spec.yarn_factor:
        return e.astype(np.float32)

    def corr(turns):
        return dim * math.log(spec.yarn_orig_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(spec.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (e * (1 - ramp) + e / spec.yarn_factor * ramp).astype(np.float32)


def rope_gain(spec: LMSpec) -> float:
    """What cos and sin are multiplied by."""
    if not spec.yarn_factor:
        return 1.0
    return _mscale(spec.yarn_factor, spec.yarn_mscale) \
        / _mscale(spec.yarn_factor, spec.yarn_mscale_all_dim)


def score_scale(spec: LMSpec) -> float:
    """What a score is multiplied by: the key's width and YaRN's
    ``m(factor, mscale_all_dim)^2`` (0.114721 at the published values)."""
    m = _mscale(spec.yarn_factor, spec.yarn_mscale_all_dim) \
        if spec.yarn_factor else 1.0
    return (spec.nope_dim + spec.rope_dim) ** -0.5 * m * m


def _rope(x, pos, spec: LMSpec):
    """x (N, ..., rope_dim) at positions pos (N,): the pairs (2i, 2i + 1)
    turned by pos * f_i; (first values | second values) out."""
    ang = pos.astype(_F32)[:, None] * jnp.asarray(yarn_freqs(spec))[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    gain = rope_gain(spec)
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# -- what both programs share ------------------------------------------------------

def _project(blk, x, pos, spec: LMSpec, dtype):
    """x (N, 1, D) at positions pos (N,): q_nope (N, H, nope), q_pe (N,
    H, rope) roped, the latent c (N, kv_rank) normed, k_pe (N, rope)
    roped (`_ranked`, `_turn`: or neither). Rows are independent: a
    decode batch and a chunk's tokens take the same path."""
    n = x.shape[0]
    u = norm(blk["ln1"], x, spec, dtype)
    cq = _ranked(blk, u, spec, dtype)
    q = proj(blk, "wqb" if spec.q_rank else "wq", cq, dtype).reshape(
        n, spec.n_heads, spec.nope_dim + spec.rope_dim)
    kv = proj(blk, "wkva", u, dtype)[:, 0]
    c = norm(blk["kv_norm"], kv[:, :spec.kv_rank], spec, dtype)
    return (q[..., :spec.nope_dim], _turn(q[..., spec.nope_dim:], pos, spec),
            c, _turn(kv[:, spec.kv_rank:], pos, spec))


def _wkvb(blk, spec: LMSpec, dtype):
    """``Wkvb`` as (kv_rank, H, nope_dim + v_dim): a head's (W_UK |
    W_UV)."""
    return blk["wkvb"].astype(dtype).reshape(
        spec.kv_rank, spec.n_heads, spec.nope_dim + spec.v_dim)


def absorbed_queries(q_nope, q_pe, w, spec: LMSpec):
    """The queries of the absorbed form, (N, H, kv_rank + rope_dim):
    ``q_nope W_UK^T`` against the latent, q_pe against the roped key."""
    q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, w[..., :spec.nope_dim],
                       preferred_element_type=_F32).astype(q_nope.dtype)
    return jnp.concatenate([q_lat, q_pe], axis=-1)


def _absorbed_out(o_lat, w, spec: LMSpec, dtype):
    """The heads' outputs from their sums of latents o_lat (N, H,
    kv_rank) f32: ``o_lat W_UV``, side by side, (N, H * v_dim)."""
    o = jnp.einsum("nhr,rhv->nhv", o_lat.astype(dtype),
                   w[..., spec.nope_dim:], preferred_element_type=_F32)
    return o.reshape(o.shape[0], -1).astype(dtype)


def _unpacked(rows, rope_dim: int):
    """Gathered rows of the roped keys' pool (..., block_size // pack,
    pack * rope_dim) as (..., slots, rope_dim) in slot order."""
    return rows.reshape(rows.shape[:-3] + (-1, rope_dim))


# -- decode -------------------------------------------------------------------

def walk_plan(block_size: int, b: int, max_blocks: int):
    """The decode walk's constants for a bucket of `b` rows: (blocks a
    chunk, chunks a full table holds, items an iteration)."""
    nb_c = max(1, min(max_blocks, DECODE_CHUNK // block_size))
    n_chunks = -(-max_blocks // nb_c)
    return nb_c, n_chunks, max(1, min(b * n_chunks, DECODE_ITEMS))


def fused_decode(block_size: int, spec: LMSpec, dtype) -> bool:
    """Whether the decode step walks a row's context in one kernel a
    layer (`pallas_paged.latent_decode_attn`) or by the work list
    (`attend_latent`): from the backend and the pools' widths alone. The
    kernel takes the latent and a packed row of two roped keys as whole
    lane tiles, whole blocks a step whose packed rows fill a 16-bit tile,
    and a pool of bfloat16 or float32."""
    return (jax.default_backend() == "tpu" and spec.kv_rank % 128 == 0
            and idx_pack(block_size, spec.rope_dim) == 2
            and (2 * spec.rope_dim) % 128 == 0 and block_size % 32 == 0
            and DECODE_STEP % block_size == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def fused_slots(pos, n: int) -> int:
    """Pool slots one layer of a decode step copies under the fused walk,
    for the bucket's positions `pos` of which the first `n` are live: each
    live row's context in whole steps of `DECODE_STEP` slots, nothing
    for the padding rows. Host arithmetic, the kernel's own trip counts."""
    return sum(int(p) // DECODE_STEP + 1 for p in pos[:n]) * DECODE_STEP


def attend_latent(q, k_pool, i_pool, li, items, t, scale: float):
    """Layer `li`'s attention in the latent of the absorbed queries q (B,
    H, kv_rank + rope_dim) over each row's work list `items`
    (`parts.live_items`): T items an iteration, all heads of an
    item's row against its chunk's latents and roped keys, the
    online-softmax carry (m, l, acc) a row and head in f32, merged through
    the (T, B) relation `own` as the dense family's. Returns the heads'
    sums of latents (B, H, kv_rank) f32."""
    row, blocks, last, n_iter = items
    b, nh, _ = q.shape
    rank = k_pool.shape[4]
    c = blocks.shape[1] * k_pool.shape[2]
    slot = jnp.arange(c)
    hi = jax.lax.Precision.HIGHEST

    def body(j, state):
        m, l, acc = state
        r = jax.lax.dynamic_slice_in_dim(row, j * t, t)
        bl = jax.lax.dynamic_slice_in_dim(blocks, j * t, t)
        la = jax.lax.dynamic_slice_in_dim(last, j * t, t)
        ct = k_pool[li, bl].reshape(t, c, rank)
        pe = _unpacked(i_pool[li, bl], q.shape[2] - rank).reshape(t, c, -1)
        qr = q[r]
        s = (jnp.einsum("thd,tcd->thc", qr[..., :rank], ct,
                        preferred_element_type=_F32)
             + jnp.einsum("thd,tcd->thc", qr[..., rank:], pe,
                          preferred_element_type=_F32)) * scale
        ok = (slot[None, :] <= la[:, None])[:, None, :]
        s = jnp.where(ok, s, -1e30)
        mi = jnp.max(s, axis=-1)                                  # (T, H)
        p = jnp.where(ok, jnp.exp(s - mi[..., None]), 0.0)
        ai = jnp.einsum("thc,tcr->thr", p.astype(ct.dtype), ct,
                        preferred_element_type=_F32)
        own = (r[:, None] == jnp.arange(b)[None, :]) & (la >= 0)[:, None]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(own[:, :, None], mi[:, None, :], -1e30), axis=0))
        w = jnp.exp(mi - m_new[r])
        old = jnp.exp(m - m_new)
        ownf = own.astype(_F32)
        l = l * old + jnp.einsum(
            "tb,th->bh", ownf, jnp.sum(p, axis=-1) * w, precision=hi)
        acc = acc * old[..., None] + jnp.einsum(
            "tb,thr->bhr", ownf, ai * w[..., None], precision=hi)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, n_iter, body, (
        jnp.full((b, nh), -1e30, _F32), jnp.zeros((b, nh), _F32),
        jnp.zeros((b, nh, rank), _F32)))
    return acc / l[..., None]


@functools.partial(jax.jit, static_argnames=("dense", "t", "spec", "dtype"))
def _decode_layer(blk, x, li, pos, live, write_blk, write_off, walk,
                  k_pool, i_pool, *, dense, t, spec, dtype):
    """Layer `li` of a decode step (jitted with `li` an argument, so a
    step traces a layer of each shape once; XLA inlines the calls).
    `walk`: the plain walk's work list, `t` (static) items an iteration;
    under the fused walk (`t` 0) the tables and the live rows' number."""
    q_nope, q_pe, c, k_pe = _project(blk, x, pos, spec, dtype)
    k_pool = k_pool.at[li, write_blk, write_off].set(
        c[:, None, :].astype(k_pool.dtype))
    i_pool = idx_write(i_pool, li, write_blk, write_off, k_pe)
    w = _wkvb(blk, spec, dtype)
    q = absorbed_queries(q_nope, q_pe, w, spec)
    if t:
        o_lat = attend_latent(q, k_pool, i_pool, li, walk, t,
                              score_scale(spec))
    else:
        # the pools go in whole: `li` is the kernel's to index with
        tables, n_live = walk
        o_lat = pallas_paged.latent_decode_attn(
            q, k_pool, i_pool, li, tables, pos, n_live,
            scale=score_scale(spec), step=DECODE_STEP)
    o = _absorbed_out(o_lat, w, spec, dtype)
    x = x + proj(blk, "wo", o[:, None, :], dtype)
    x, load = shared_mlp(blk, norm(blk["ln2"], x, spec, dtype), live, dense,
                         spec, dtype, onto=x)
    return x, load, k_pool, i_pool


def latent_moe_decode_step(params, cur, tables, pos, n_live, k_pool, i_pool,
                           *, spec: LMSpec, dtype=jnp.float32):
    """One decode step for a bucketed batch, in the absorbed form. cur,
    pos (B_b,) int32; tables (B_b, max_blocks) int32; n_live () int32,
    the real rows (the first ones). Returns (logits (B_b, vocab) f32, the
    expert layers' counts (layers, experts_held + 1) int32, k_pool,
    i_pool)."""
    b = cur.shape[0]
    bs = k_pool.shape[2]
    write_blk = tables[jnp.arange(b), pos // bs]
    write_off = pos % bs
    live = jnp.arange(b) < n_live
    if fused_decode(bs, spec, k_pool.dtype):
        walk, t = (tables, n_live), 0
    else:
        # one work list a step, shared by every layer
        nb_c, n_chunks, t = walk_plan(bs, b, tables.shape[1])
        walk = parts.live_items(tables, pos, bs, nb_c, n_chunks, t)
    x = params["embed"][cur][:, None, :].astype(dtype)
    load = []
    for li, blk in enumerate(params["blocks"]):
        x, counts, k_pool, i_pool = _decode_layer(
            blk, x, li, pos, live, write_blk, write_off, walk, k_pool,
            i_pool, dense=li < spec.dense_layers, t=t, spec=spec,
            dtype=dtype)
        if counts is not None:
            load.append(counts)
    return (finish(params, x[:, 0], dtype, spec.norm_eps), jnp.stack(load),
            k_pool, i_pool)


# -- chunk prefill ------------------------------------------------------------

def expanded_attend(c: int, spec: LMSpec) -> bool:
    """Whether a chunk of `c` queries attends in the expanded form: from
    the bucket and the widths alone, by the operations. A (query, key)
    costs 2 H (2 kv_rank + rope_dim) absorbed and 2 H (nope_dim +
    rope_dim + v_dim) expanded; a key's expansion, once for all the
    queries, 2 kv_rank H (nope_dim + v_dim)."""
    absorbed = 2 * spec.kv_rank + spec.rope_dim
    expanded = spec.nope_dim + spec.rope_dim + spec.v_dim
    return c * (absorbed - expanded) > spec.kv_rank * (spec.nope_dim
                                                       + spec.v_dim)


def attend_tiles(q_nope, q_pe, qpos, tab, span, li, k_pool, i_pool, w, *,
                 expanded: bool, fused: bool, tile: int, spec: LMSpec,
                 dtype):
    """Layer `li`'s attention of a whole chunk: queries (C, H, .) at
    positions qpos (C,) of one sequence over the context tiles `span`
    (first, end; traced) of its table `tab` (MB,), a tile's latents and
    roped keys read once for all queries, under the causal edge (qpos are
    consecutive positions, which the fused update makes its mask from).
    `expanded`: the tile goes through `w` (`_wkvb`) to a K and a V a
    head; else the queries go through it to the latent. Returns (C, H *
    v_dim) in `dtype`."""
    c, nh, _ = q_nope.shape
    bs, rank = k_pool.shape[2], spec.kv_rank
    nope, rope = spec.nope_dim, spec.rope_dim
    tab = parts.whole_tiles(tab, tile, bs)
    if expanded:
        # a head is a KV head of its own; the kernel takes a head's K as
        # whole lane tiles, so its width is filled up with zeros
        fill = -(nope + rope) % 128 if fused else 0
        q = jnp.concatenate([q_nope, q_pe] + [jnp.zeros(
            (c, nh, fill), q_pe.dtype)] * bool(fill), axis=-1)
        heads, vw = (nh, 1), spec.v_dim
    else:
        fill, q = 0, absorbed_queries(q_nope, q_pe, w, spec)
        heads, vw = (1, nh), rank
    kw = q.shape[-1]
    # the tile update divides a score by the root of the width it sees
    qg = (q * (score_scale(spec) * kw ** 0.5)).astype(dtype).reshape(
        (c,) + heads + (kw,))
    # the kernel's layout, a head's queries side by side: made once
    qh = qg.transpose(1, 2, 0, 3) if fused else None

    def read(bl):
        ct = k_pool[li, bl].astype(dtype).reshape(tile, rank)
        pe = _unpacked(i_pool[li, bl], rope).astype(dtype).reshape(tile, rope)
        if expanded:
            kv = jnp.einsum("sr,rhd->shd", ct, w,
                            preferred_element_type=_F32).astype(dtype)
            kt = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(pe[:, None, :], (tile, nh, rope))]
                + [jnp.zeros((tile, nh, fill), dtype)] * bool(fill), axis=-1)
            vt = kv[..., nope:]
        else:
            kt = jnp.concatenate([ct, pe], axis=-1)[:, None, :]
            vt = ct[:, None, :]
        return kt, vt

    def update(j, kt, vt, state):
        return parts.causal_update(qg, qh, kt, vt, qpos, j * tile, state,
                                   fused=fused)

    # a padding query past the table's last tile attended nothing
    att = parts.walk_tiles(tab, span, tile // bs, read, update, heads, c,
                           vw, l_floor=1e-30).reshape(nh, c, vw)
    att = att.transpose(1, 0, 2)
    if expanded:
        return att.reshape(c, nh * vw).astype(dtype)
    return _absorbed_out(att, w, spec, dtype)


@functools.partial(jax.jit, static_argnames=(
    "dense", "tile", "by_block", "fused", "expanded", "spec", "dtype"))
def _chunk_layer(blk, x, li, pos, live, blk_idx, blk_off, tab, k_pool,
                 i_pool, *, dense, tile, by_block, fused, expanded, spec,
                 dtype):
    """Layer `li` of a chunk: x (C, 1, D), the chunk's tokens as rows
    (jitted with `li` an argument, as `_decode_layer`)."""
    c = x.shape[0]
    bs = k_pool.shape[2]
    q_nope, q_pe, lat, k_pe = _project(blk, x, pos, spec, dtype)
    k_pool = write_chunk(k_pool, li, blk_idx, blk_off, lat[:, None, :],
                         by_block)
    i_pool = write_chunk(i_pool, li, blk_idx, blk_off, k_pe, by_block)
    span = parts.tile_span(pos[0], c, tab.shape[0] * bs, tile)
    o = attend_tiles(q_nope, q_pe, pos, tab, span, li, k_pool, i_pool,
                     _wkvb(blk, spec, dtype), expanded=expanded, fused=fused,
                     tile=tile, spec=spec, dtype=dtype)
    x = x + proj(blk, "wo", o[:, None, :], dtype)
    x, load = shared_mlp(blk, norm(blk["ln2"], x, spec, dtype), live, dense,
                         spec, dtype, onto=x)
    return x, load, k_pool, i_pool


def latent_moe_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table,
                             k_pool, i_pool, last_idx, *, spec: LMSpec,
                             dtype=jnp.float32, by_block: bool = False,
                             fused: bool = False, expanded: bool = False,
                             tile: int = parts.CTX_TILE):
    """One prompt chunk of one sequence: the arguments of
    `paged_prefill_chunk` with the roped keys' pool where V would be.
    `by_block` (static): the caller vouches that `pos0` and the chunk's
    width are multiples of the block size (`parts.write_chunk`); `fused`
    (static): the walk updates a tile in one kernel, and the caller asks
    `parts.fused_attend` whether it may; `expanded` (static): the
    attention's form, which the caller asks `expanded_attend`; `tile`
    (static): the context slots a walk covers an iteration. Returns (last
    real token's logits (vocab,) f32, the expert layers' counts over the
    chunk's real tokens (layers, experts_held + 1) int32, k_pool, i_pool)."""
    c = ids.shape[1]
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    x = params["embed"][ids[0]][:, None, :].astype(dtype)
    load = []
    for li, blk in enumerate(params["blocks"]):
        x, counts, k_pool, i_pool = _chunk_layer(
            blk, x, li, pos, live, blk_idx, blk_off, table, k_pool, i_pool,
            dense=li < spec.dense_layers, tile=tile, by_block=by_block,
            fused=fused, expanded=expanded, spec=spec, dtype=dtype)
        if counts is not None:
            load.append(counts)
    logits = finish(params, x[last_idx, 0][None, :], dtype,
                    spec.norm_eps)[0]
    return logits, jnp.stack(load), k_pool, i_pool


# -- what the spec may leave out, and the layers under public names ------------
# Below every kernel's call site: a kernel's serialized module carries the
# lines of its call sites, so a line added above them costs the cells that
# hold the kernel one compile (PERF.md section 6, PR 44).

def _ranked(blk, u, spec: LMSpec, dtype):
    """What the query's heads are made from: the normed `q_rank` values
    ``RMSNorm(u Wqa)`` (through ``Wqb``), or under `q_rank` 0 the layer's
    input itself (through ``Wq``: no low-rank step and no norm)."""
    if not spec.q_rank:
        return u
    return norm(blk["q_norm"], proj(blk, "wqa", u, dtype), spec, dtype)


def _turn(x, pos, spec: LMSpec):
    """`_rope`, or x as it is where the model turns nothing (`roped`
    false: the `rope_dim` values ride beside the others unturned)."""
    return _rope(x, pos, spec) if spec.roped else x


#: a whole latent layer of a decode step and of a chunk (attention, then the
#: MLP `experts.shared_mlp` makes of `dense`), for a family that has such
#: layers among others (llm/delta_moe.py); jitted with the layer's index
#: in the pools an argument, as this family's own programs call them
decode_layer, chunk_layer = _decode_layer, _chunk_layer
