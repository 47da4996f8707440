"""The sparse-expert decoder whose attention a learned indexer chooses
(pure jax, jitted by llm_exec as ``jit_sparse_moe_decode_step`` and
``jit_sparse_moe_prefill_chunk``).

What is new in this family lives here and nowhere else: the indexer, the
exact selection, attention over the selected slots, the dropless expert
layer, and the two entry points. The projections (`_proj`), the norms
(`rmsnorm`), the rope (`_rope_rows`, with the model's own base) and the
write into the pool are the dense family's functions.

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
  chunk walks the context written so far in tiles of `_CTX_TILE` slots,
  a loop whose trip count comes from ``pos0`` (one program whatever the
  prompt's length): index scores of each tile are kept as order-
  preserving integer keys ``(C, S)``, the k-th largest key of each query
  is found exactly by a search over its 32 bits (ties cut at the lower
  positions by a second search, run only when a tie straddles the cut),
  and attention walks the same tiles with an online softmax under the
  mask ``key > T or (key == T and position <= P)``. No
  ``(heads, chunk, max_len)`` score tensor exists. How a tile updates
  the softmax's carry is chosen by `fused_attend` from the backend and
  the shapes: on a TPU, where the head width is a whole lane tile, one
  kernel a tile (`pallas_ops.selected_block_update`: scores, mask,
  running maximum and sum, exponentials and the value product of a
  block of queries stay in fast memory, and nothing of shape
  ``(heads, C, _CTX_TILE)`` is written); elsewhere `attend_plain`, whose
  tile of float32 scores passes through memory three times. The two
  attend the same slots and differ in the order float32 sums are added
  inside a tile. XLA still reads the pool through the table for both:
  ``paged_kernel`` stays ``xla``.
- Expert layer (both): (token, expert) pairs sorted by expert, two
  grouped products over the experts that have tokens, combined by the
  renormalised weights in f32. Experts without a token are not read.
  Padding rows are routed past the last expert and count for nothing.
  Returns the tokens each expert got, which rides the step's read-back.
  Which product (`_grouped`, from the static count of pair rows alone):
  a grouped product visits every (row tile, expert) pair that shares
  rows, a whole row tile against that expert's matrices each time, and
  the TPU compiler gives `jax.lax.ragged_dot` a row tile of min(512,
  pair rows). Up to 512 pair rows (`_XLA_ROW_TILE`: every decode bucket
  of both families, a chunk bucket of up to 512 / `experts_per_tok`
  tokens) that tile is all the rows and the call stays
  `jax.lax.ragged_dot`, its rows filled up to a multiple of 8
  (`_GROUPED_ROWS`) past the last group. Beyond (a chunk of 2,048
  tokens: 8,192 or 16,384 pair rows, of which an expert gets tens to a
  few hundred) the matrix unit would be paid 512 rows a visit, so the
  call goes to `pallas_ops.grouped_matmul`, the same grid with the row
  tile `expert_row_tile` reckons from (pair rows, `n_experts`): 128 or
  256. Same operands, float32 sums, every pair at every held expert;
  the two differ in the order float32 sums are added inside a K tile.
  The kernel leaves the rows past the last group unwritten: the
  combine's mask is their only reader.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm.paged_model import _proj, _rope_rows
from nnstreamer_tpu.llm.spec import LMSpec
from nnstreamer_tpu.models.transformer import rmsnorm

# Context slots one iteration of the chunk program's walks covers (a
# whole number of blocks). The plain attention update keeps a tile's
# (heads, C, tile) f32 scores in memory, 268 MB at 32 heads and C 2048;
# the fused one keeps a block of them in fast memory, and the program's
# largest temporary is then the (C, max_len) integer keys.
_CTX_TILE = 1024

# Queries a program of the fused update takes at once, against a whole
# tile (`pallas_ops.selected_block_update`; read on the chip, PERF.md
# PR 32).
_FUSED_Q_BLOCK = 128

# Pair rows of a grouped product the TPU compiler hands to its kernel:
# a multiple of this. Any other count it expands to one dense product
# over all groups (compiled for a described v5e, and read on the chip:
# PERF.md, PR 39).
_GROUPED_ROWS = 8

# The row tile the TPU compiler gives `jax.lax.ragged_dot`'s kernel: all
# the pair rows up to this many, and this many beyond (read from the
# compiled text's `ragged_dot_tiling`: PERF.md, PR 40). A visit of the
# kernel is a whole row tile against one expert's matrices, so past this
# count an expert's few rows are paid for as 512.
_XLA_ROW_TILE = 512

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
    qkv = _proj(blk, "wqkv", h, dtype)
    qw, kw = nh * hd, nkv * hd
    q = qkv[..., :qw].reshape(n, 1, nh, hd)
    k = qkv[..., qw:qw + kw].reshape(n, 1, nkv, hd)
    v = qkv[..., qw + kw:].reshape(n, 1, nkv, hd)
    if spec.qk_norm:
        q = rmsnorm(q, blk["q_norm"].astype(dtype))
        k = rmsnorm(k, blk["k_norm"].astype(dtype))
    q = _rope_rows(q, pos, spec.rope_theta)
    k = _rope_rows(k, pos, spec.rope_theta)
    idx = _proj(blk, "widx", h, dtype)
    qi = _rope_rows(idx[..., :hi * di].reshape(n, 1, hi, di), pos,
                    spec.rope_theta)
    ki = _rope_rows(idx[..., hi * di:hi * di + di].reshape(n, 1, 1, di),
                    pos, spec.rope_theta)
    w = idx[..., hi * di + di:].reshape(n, hi).astype(_F32)
    return q[:, 0], k[:, 0], v[:, 0], qi[:, 0], ki[:, 0, 0], w


def _idx_write(i_pool, li, blk, off, ki):
    """Write indexer keys ki (N, di) to slots `off` (N,) of blocks `blk`
    (N,) of layer `li`: slot s is row s // pack, values (s % pack) * di
    onward."""
    di = ki.shape[1]
    pack = i_pool.shape[3] // di
    at = jnp.stack([jnp.full_like(blk, li), blk, off // pack,
                    (off % pack) * di], axis=1)
    return jax.lax.scatter(
        i_pool, at, ki.astype(i_pool.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2, 3)),
        indices_are_sorted=False, unique_indices=True,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _put_blocks(pool, li, first, x):
    """Whole blocks x (values of len(first) blocks, in order) into layer
    `li` of `pool` at the blocks `first` (nb,): one in-place update a
    block, in a loop (as one scatter of whole blocks XLA:TPU re-lays the
    whole pool for it, 2.6 GB)."""
    nb = first.shape[0]
    x = x.astype(pool.dtype).reshape((nb, 1, 1) + pool.shape[2:])
    zeros = (0,) * (pool.ndim - 2)
    return jax.lax.fori_loop(0, nb, lambda i, p: (
        jax.lax.dynamic_update_slice(p, x[i], (li, first[i]) + zeros)),
        pool)


def _write_chunk(pools, li, blk_idx, blk_off, k, v, ki, by_block: bool):
    """A chunk's keys, values and indexer keys (C rows, consecutive
    positions) into the three pools. `by_block`: the chunk starts on a
    block's first slot and is a whole number of blocks long, so each
    block is written whole, C / block_size writes a pool and not C (a
    scatter runs its updates one after another: 2048 of them were 10 ms
    a layer). A block the prompt ends in takes its padding rows' values
    in the slots past the end, which are written again before any query
    may read them."""
    k_pool, v_pool, i_pool = pools
    bs = k_pool.shape[2]
    if not by_block:
        return (k_pool.at[li, blk_idx, blk_off].set(k.astype(k_pool.dtype)),
                v_pool.at[li, blk_idx, blk_off].set(v.astype(v_pool.dtype)),
                _idx_write(i_pool, li, blk_idx, blk_off, ki))
    first = blk_idx.reshape(k.shape[0] // bs, bs)[:, 0]
    return (_put_blocks(k_pool, li, first, k),
            _put_blocks(v_pool, li, first, v),
            _put_blocks(i_pool, li, first, ki))


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


def _route(blk, g, spec: LMSpec, dtype):
    """The router for tokens g (N, D): (weights (N, k) f32, experts
    (N, k) int32 among all `n_experts`). Softmax scores: the k largest,
    renormalised; under `n_group` > 1 the k largest inside the
    `topk_group` groups whose best expert scores highest (ties to the
    lower index, of groups and of experts), and where `route_norm` is
    false the scores as they are, times `route_scale`. Sigmoid scores:
    the k of largest score + bias (ties to the lower index), weighted by
    their scores alone, renormalised and multiplied by `route_scale`."""
    k = spec.experts_per_tok
    logits = jnp.dot(g, blk["router"].astype(dtype),
                     preferred_element_type=_F32)
    if spec.score_fn == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        if spec.n_group > 1:
            # the other groups' scores count as 0: below every real one
            best = jnp.max(s.reshape(-1, spec.n_group,
                                     s.shape[-1] // spec.n_group), axis=-1)
            _, chosen = jax.lax.top_k(best, spec.topk_group)
            group = jnp.arange(s.shape[-1]) // (s.shape[-1] // spec.n_group)
            s = jnp.where(jnp.any(
                group[None, None, :] == chosen[:, :, None], axis=1), s, 0.0)
        p, e = jax.lax.top_k(s, k)
        if not spec.route_norm:
            return spec.route_scale * p, e
        return p / jnp.sum(p, axis=-1, keepdims=True), e
    s = jax.nn.sigmoid(logits)
    _, e = jax.lax.top_k(s + blk["router_bias"].astype(_F32), k)
    p = jnp.take_along_axis(s, e, axis=-1)
    return spec.route_scale * p / (
        jnp.sum(p, axis=-1, keepdims=True) + 1e-20), e


def _fit(size: int, want: int) -> int:
    """The widest tile of at most `want` that divides `size` in whole
    lane tiles of 128; the whole of a `size` that has none."""
    t = min(want, size) // 128 * 128
    while t and size % t:
        t -= 128
    return t or size


def expert_row_tile(rows: int, n_experts: int) -> int:
    """The row tile of the expert layer's grouped products over `rows`
    (token, expert) pair rows dealt over `n_experts`: from the shapes
    alone. Up to `_XLA_ROW_TILE` rows it is the compiler's own, all the
    rows (filled to `_GROUPED_ROWS`). Beyond, a visit is one row tile
    against one expert's matrices, and on the v5e it is bound by reading
    those matrices up to about 240 rows (197 TFLOP/s over 819 GB/s) and
    by the matrix unit past that: the tile is a few times the mean rows
    an expert gets, so that an expert's rows span one or two tiles,
    within 128 to 256 (the sweep on the chip: PERF.md, PR 40)."""
    if rows <= _XLA_ROW_TILE:
        return -(-rows // _GROUPED_ROWS) * _GROUPED_ROWS
    mean = max(1, rows // n_experts)
    return min(256, max(128, 1 << (2 * mean - 1).bit_length()))


def _grouped(xs, w, counts, n_experts: int):
    """The grouped product ``xs[rows of expert e] @ w[e]`` for pair rows
    xs (R, K) sorted by expert, w (E, K, N), counts (E,): the compiler's
    kernel where its row tile is all the rows, the repo's kernel with
    `expert_row_tile`'s beyond. Rows past the last expert's are the
    caller's to leave unread."""
    rows, (_, kk, nn) = xs.shape[0], w.shape
    if rows <= _XLA_ROW_TILE:
        return jax.lax.ragged_dot(xs, w, counts)
    # a (tk, tn) tile of an expert's matrix is 2 MB whatever the type
    tk = _fit(kk, 2048 // xs.dtype.itemsize)
    return pallas_ops.grouped_matmul(
        xs, w, counts,
        tiling=(expert_row_tile(rows, n_experts), tk, _fit(nn, 1024)))


def _expert_layer(blk, g, live, spec: LMSpec, dtype):
    """The dropless expert layer for tokens g (N, D), `live` (N,) bool
    marking the real ones. The router scores all `n_experts`; `ewi` and
    `ewd` carry the experts held here, `experts_held` from
    `experts_first` on (all of them where `experts_held` is 0), and a
    pair routed to an expert that is not held costs nothing and adds
    nothing. Returns (y (N, D) in `dtype`, tokens each held expert got
    (held,) int32, the real tokens' pairs routed away () int32)."""
    n, d = g.shape
    k, f = spec.experts_per_tok, spec.expert_width
    ne = spec.experts_held or spec.n_experts
    p, e = _route(blk, g, spec, dtype)
    # a padding row's pairs sort past the last expert and belong to no
    # group: they cost no expert's weights and are not counted; so do
    # the pairs of an expert that is not held here
    mine = live[:, None]
    if spec.experts_held:
        e = e - spec.experts_first
        mine = mine & (e >= 0) & (e < ne)
    e = jnp.where(mine, e, ne).reshape(-1)
    order = jnp.argsort(e, stable=True)
    counts = jnp.sum(e[:, None] == jnp.arange(ne)[None, :], axis=0,
                     dtype=jnp.int32)
    xs = g[order // k]
    # a count of pair rows the compiler would expand (one decode row of
    # 4 a token) reads every held expert, and reads wrong in float32 at
    # `highest` on the chip: rows of zeros past the last group, which
    # belong to no expert, take it to the kernel
    if n * k % _GROUPED_ROWS:
        xs = jnp.pad(xs, ((0, -(n * k) % _GROUPED_ROWS), (0, 0)))
    gu = _grouped(xs, blk["ewi"].astype(dtype), counts, spec.n_experts)
    mid = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    out = _grouped(mid, blk["ewd"].astype(dtype), counts, spec.n_experts)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    # the rows past the last group are read by no pair that is `mine`:
    # this `where` is their only reader, and the kernel leaves them
    # unwritten
    out = out[inv].reshape(n, k, d).astype(_F32)
    y = jnp.sum(jnp.where(mine[..., None], out * p[..., None], 0.0),
                axis=1)
    away = jnp.sum(live[:, None] & ~mine, dtype=jnp.int32)
    return y.astype(dtype), counts, away


def _finish(params, x, dtype):
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    return _proj(params, "head", x, dtype).astype(_F32)


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
    i_pool = _idx_write(i_pool, li, write_blk, write_off, ki)
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
    x = x + _proj(blk, "wo", att.reshape(b, 1, -1), dtype)
    g = rmsnorm(x, blk["ln2"].astype(dtype))
    y, counts, _ = _expert_layer(blk, g[:, 0], live, spec, dtype)
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
    return (_finish(params, x[:, 0], dtype), jnp.stack(load),
            k_pool, v_pool, i_pool)


# -- chunk prefill ------------------------------------------------------------

def _sort_keys(x):
    """f32 -> uint32 with the same order (and -0.0 == +0.0: a tie, as
    in `select_rows`). Every real number's key is
    above 0, which is kept for slots a query may not attend."""
    u = jax.lax.bitcast_convert_type(x, _U32)
    u = jnp.where(u == _U32(0x80000000), _U32(0), u)
    return jnp.where(u >> 31 == 1, ~u, u | _U32(0x80000000))


def _count(keys, n_tiles, tile, hits):
    """Per query, over the first `n_tiles` context tiles (of `tile`
    slots) of keys (C, S): how many slots each of the masks
    `hits(keys_tile, first_slot)` (a tuple) marks; one read of the keys
    for all of them."""
    c = keys.shape[0]

    def body(j, acc):
        kt = jax.lax.dynamic_slice_in_dim(keys, j * tile, tile, 1)
        return tuple(a + jnp.sum(h, axis=1, dtype=jnp.int32)
                     for a, h in zip(acc, hits(kt, j * tile)))

    n = len(hits(keys[:, :tile], 0))
    return jax.lax.fori_loop(0, n_tiles, body,
                             (jnp.zeros((c,), jnp.int32),) * n)


def select_cut(keys, n_tiles, tile, k_eff):
    """Exact selection for a chunk. keys (C, S) uint32 (0 where a query
    may not attend) in `n_tiles` live tiles of `tile` slots, k_eff (C,)
    how many each query takes (no more than it may attend). Returns (T (C,) uint32, P (C,) int32): query c takes
    the slots with ``key > T[c]``, and those with ``key == T[c]`` at
    positions ``<= P[c]``: the k_eff slots of largest key, ties to the
    lower position."""
    c, s = keys.shape

    def digit_step(i, t):
        # two bits a read of the keys: the largest of the digit's three
        # non-zero values that still leaves k_eff keys at or above
        shift = _U32(30) - 2 * i.astype(_U32)
        cands = [t | (_U32(d) << shift) for d in (1, 2, 3)]
        ns = _count(keys, n_tiles, tile, lambda kt, _: tuple(
            kt >= cand[:, None] for cand in cands))
        for cand, n in zip(cands, ns):
            t = jnp.where(n >= k_eff, cand, t)
        return t

    # the largest T with at least k_eff keys >= T: the k_eff-th largest
    t = jax.lax.fori_loop(0, 16, digit_step, jnp.zeros((c,), _U32))
    above, ties = _count(keys, n_tiles, tile, lambda kt, _: (
        kt > t[:, None], kt == t[:, None]))
    need = k_eff - above                # of the ties, the lowest `need`

    def cut_ties():
        bits = max(1, (s - 1).bit_length())

        def pos_step(i, p):
            cand = p | (jnp.int32(1) << (bits - 1 - i))
            n, = _count(keys, n_tiles, tile, lambda kt, s0: (
                (kt == t[:, None]) & ((s0 + jnp.arange(tile))[None, :]
                                      < cand[:, None]),))
            return jnp.where(n < need, cand, p)

        # the largest P with fewer than `need` ties below it: the
        # position of the need-th tie
        return jax.lax.fori_loop(0, bits, pos_step,
                                 jnp.zeros((c,), jnp.int32))

    p = jax.lax.cond(jnp.any(need != ties), cut_ties,
                     lambda: jnp.full((c,), s, jnp.int32))
    return t, p


def fused_attend(c: int, tile: int, hd: int) -> bool:
    """Whether a chunk of `c` queries walks its context tiles of `tile`
    slots with the fused update (`pallas_ops.selected_block_update`) or
    the plain one (`attend_plain`): from the backend and the shapes
    alone. The kernel takes a KV head as a lane tile (hd and the tile
    multiples of 128) and whole blocks of queries."""
    return (jax.default_backend() == "tpu" and hd % 128 == 0
            and tile % 128 == 0 and c % min(_FUSED_Q_BLOCK, c) == 0)


def attend_plain(qg, kt, vt, key_t, t, cut, first, state):
    """One context tile of the chunk's attention walk in plain XLA: qg
    (C, Hkv, G, hd); kt, vt (tile, Hkv, hd), the slots from `first` on;
    key_t (C, tile) their selection keys; query c attends the slots with
    ``key > t[c]`` and those with ``key == t[c]`` at positions
    ``<= cut[c]``. state: the online softmax's m, l (Hkv, G, C) and acc
    (Hkv, G, C, hd), f32. The scores of the tile, (Hkv, G, C, tile) f32,
    pass through memory three times."""
    m, l, acc = state
    tile, hd = kt.shape[0], kt.shape[2]
    sel = (key_t > t[:, None]) | ((key_t == t[:, None]) & (
        (first + jnp.arange(tile))[None, :] <= cut[:, None]))
    sel = sel[None, None]                         # (1, 1, C, tile)
    s = jnp.einsum("cgrd,sgd->grcs", qg, kt,
                   preferred_element_type=_F32) * hd ** -0.5
    s = jnp.where(sel, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.where(sel, jnp.exp(s - m_new[..., None]), 0.0)
    old = jnp.exp(m - m_new)
    l = l * old + jnp.sum(p, axis=-1)
    acc = acc * old[..., None] + jnp.einsum(
        "grcs,sgd->grcd", p.astype(vt.dtype), vt,
        preferred_element_type=_F32)
    return m_new, l, acc


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
    k_pool, v_pool, i_pool = _write_chunk(
        (k_pool, v_pool, i_pool), li, blk_idx, blk_off, k, v, ki, by_block)
    slot = jnp.arange(tile)

    def tile_blocks(j):
        return jax.lax.dynamic_slice_in_dim(tab, j * nb_t, nb_t)

    def score_tile(j, keys):
        rows = i_pool[li, tile_blocks(j)].astype(dtype)
        sc = _idx_scores(qi, w, rows.reshape(-1, rows.shape[-1]),
                         spec.idx_dim, shared=True)             # (C, tile)
        may = (j * tile + slot)[None, :] <= pos[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            keys, jnp.where(may, _sort_keys(sc), _U32(0)), j * tile, 1)

    keys = jax.lax.fori_loop(0, n_tiles, score_tile,
                             jnp.zeros((c, s_pad), _U32))
    k_eff = jnp.minimum(min(int(spec.topk), s_pad), pos + 1)
    t, cut = select_cut(keys, n_tiles, tile, k_eff)
    qg = q.reshape(c, nkv, grp, hd)
    # the kernel's layout, a head's queries side by side: made once
    qh = qg.transpose(1, 2, 0, 3) if fused else None

    def attend_tile(j, state):
        bl = tile_blocks(j)
        kt = k_pool[li, bl].astype(dtype).reshape(tile, nkv, hd)
        vt = v_pool[li, bl].astype(dtype).reshape(tile, nkv, hd)
        if fused:
            return pallas_ops.selected_block_update(
                qh, kt, vt, keys, t, cut, j, *state, block_q=_FUSED_Q_BLOCK)
        key_t = jax.lax.dynamic_slice_in_dim(keys, j * tile, tile, 1)
        return attend_plain(qg, kt, vt, key_t, t, cut, j * tile, state)

    m, l, acc = jax.lax.fori_loop(0, n_tiles, attend_tile, (
        jnp.full((nkv, grp, c), -1e30, _F32),
        jnp.zeros((nkv, grp, c), _F32),
        jnp.zeros((nkv, grp, c, hd), _F32)))
    att = (acc / l[..., None]).transpose(2, 0, 1, 3).astype(dtype)
    x = x + _proj(blk, "wo", att.reshape(c, 1, -1), dtype)
    g = rmsnorm(x, blk["ln2"].astype(dtype))
    y, counts, _ = _expert_layer(blk, g[:, 0], live, spec, dtype)
    return x + y[:, None, :], counts, k_pool, v_pool, i_pool


def sparse_moe_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table,
                             k_pool, v_pool, i_pool, last_idx,
                             *, spec: LMSpec, dtype=jnp.float32,
                             by_block: bool = False, fused: bool = False):
    """One prompt chunk of one sequence; the arguments of
    `paged_prefill_chunk` with the indexer's pool after K and V.
    `by_block` (static): the caller vouches that `pos0` and the chunk's
    width are multiples of the block size (`_write_chunk`). `fused`
    (static): the attention walk updates a tile in one kernel; the
    caller asks `fused_attend` whether it may.
    Returns (last real token's logits (vocab,) f32, tokens an expert
    (L, E) int32 over the chunk's real tokens, k_pool, v_pool, i_pool).
    """
    c = ids.shape[1]
    bs = k_pool.shape[2]
    tile = _CTX_TILE
    if tile % bs:
        raise ValueError(f"block_size {bs} does not divide the context "
                         f"tile of {tile} slots")
    nb_t = tile // bs
    max_tiles = -(-table.shape[0] // nb_t)
    # the table's tail past max_blocks reads block 0: the scratch block
    tab = jnp.pad(table, (0, max_tiles * nb_t - table.shape[0]))
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    n_tiles = jnp.minimum((pos0 + c + tile - 1) // tile, max_tiles)
    x = params["embed"][ids[0]][:, None, :].astype(dtype)     # (C, 1, D)
    load = []
    for li, blk in enumerate(params["blocks"]):
        x, counts, k_pool, v_pool, i_pool = _chunk_layer(
            blk, x, li, pos, live, blk_idx, blk_off, tab, n_tiles,
            k_pool, v_pool, i_pool, tile=tile, by_block=by_block,
            fused=fused, spec=spec, dtype=dtype)
        load.append(counts)
    logits = _finish(params, x[last_idx, 0][None, :], dtype)[0]
    return logits, jnp.stack(load), k_pool, v_pool, i_pool
