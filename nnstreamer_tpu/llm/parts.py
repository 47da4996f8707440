"""What a decoder layer is made of, whatever its family (pure jax).

The one home of what two or more of the family modules use
(`paged_model`, `sparse_moe`, `hybrid_lm`, `window_moe`, `latent_moe`),
under public names: a family module imports this one and `experts`, never
another family (docs/llm_serving.md says which uses which). Below this
module are the kernels alone (`backends/pallas_ops.py`, imported where a
function calls one, so that the dense family's programs load no Pallas).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.transformer import rmsnorm

_F32 = jnp.float32
_U32 = jnp.uint32


# -- the dense products -------------------------------------------------------

def proj(store, name, x, dtype):
    """One projection matmul, quant-aware: a store version whose params
    carry ``<name>_scale`` (models/quant.quantize_transformer) routes
    through the W8A8 int8 path; float params take the dense matmul the
    reference always took — for float weights this is bit-identical to
    the inline ``x @ w`` it replaced, so the parity contract is
    untouched."""
    if f"{name}_scale" in store:
        from nnstreamer_tpu.models.quant import w8a8_matmul

        return w8a8_matmul(x, store[name],
                           store[f"{name}_scale"]).astype(dtype)
    return x @ store[name].astype(dtype)


def mlp_paged(blk, x, dtype):
    """SwiGLU MLP through `proj` — the quant-aware twin of
    `transformer._mlp` (identical math for float params)."""
    gate_up = proj(blk, "wi", x, dtype)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return proj(blk, "wd", jax.nn.silu(gate) * up, dtype)


def rope_rows(x, pos, base=10000.0):
    """Rotary embedding with a PER-ROW position: x (B, 1, H, D),
    pos (B,). Same f32 angle math as `transformer.rope`, broadcast over
    the batch instead of the sequence axis — row b's values are bit-
    identical to rope(x[b:b+1], pos[b:b+1], base)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]   # (B, half)
    cos = jnp.cos(ang)[:, None, None, :]
    sin = jnp.sin(ang)[:, None, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def norm(w, x, spec, dtype):
    """RMSNorm of x by the weights `w` under the spec's `norm_eps`."""
    return rmsnorm(x, w.astype(dtype), spec.norm_eps)


def finish(params, x, dtype, eps=1e-6, logit_div=None):
    """The program's last step: the final norm of x (N, D) (`eps`: the
    family's `norm_eps`; `rmsnorm`'s own where it has none), divided by
    `logit_div` where the family has one, through the head. Logits (N,
    vocab) f32."""
    x = rmsnorm(x, params["ln_f"].astype(dtype), eps)
    if logit_div is not None:
        x = (x / logit_div).astype(dtype)
    return proj(params, "head", x, dtype).astype(_F32)


def layer_index(kinds) -> list:
    """For each layer of `kinds` its index among the layers of its own
    kind: where its state or its K and V live in that kind's pools."""
    seen = {}
    out = []
    for kind in kinds:
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return out


# -- a chunk's writes into the pools ------------------------------------------

def idx_write(i_pool, li, blk, off, ki):
    """Write packed rows ki (N, w) (indexer keys, roped keys) to slots
    `off` (N,) of blocks `blk` (N,) of layer `li`: slot s is row
    s // pack, values (s % pack) * w onward."""
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


def put_blocks(pool, li, first, x):
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


def write_chunk(pool, li, blk_idx, blk_off, x, by_block: bool):
    """A chunk's rows x, consecutive positions, into layer `li` of `pool`:
    x (C, Hkv, hd) into a pool of heads ``(L, blocks, block_size, Hkv,
    hd)``, x (C, w) into a packed one ``(L, blocks, block_size // pack,
    pack * w)`` (`idx_write`). `by_block`: the chunk starts on a block's
    first slot and is a whole number of blocks long, so each block is
    written whole, C / block_size writes and not C (a scatter runs its
    updates one after another: 2048 of them were 10 ms a layer). A block
    the prompt ends in takes its padding rows' values in the slots past
    the end, which are written again before any query may read them."""
    packed = pool.ndim == 4
    if not by_block:
        if packed:
            return idx_write(pool, li, blk_idx, blk_off, x)
        return pool.at[li, blk_idx, blk_off].set(x.astype(pool.dtype))
    bs = pool.shape[2] * (pool.shape[3] // x.shape[-1] if packed else 1)
    first = blk_idx.reshape(x.shape[0] // bs, bs)[:, 0]
    return put_blocks(pool, li, first, x)


# -- the decode step's work list ----------------------------------------------

# The extents of `walk_plan`, set from chip runs (PERF.md section 6,
# PR 26 and PR 30). They are bytes of the float32 tile the products
# read, not of the pool: a narrower pool is widened after its gather,
# inside the loop, and it is the widened K and V tiles of an iteration
# that have to stay in fast memory (at twice these slots an iteration a
# bfloat16 pool's step was slower than a float32 pool's at these). One K
# (or V) chunk is at most `CHUNK_BYTES` of it, one iteration's chunks
# together at most `ITER_BYTES`.
CHUNK_BYTES = 128 << 10
ITER_BYTES = 16 << 20


def walk_plan(block_size, n_kv, hd, b, max_blocks):
    """The decode walk's constants for one pool geometry and bucket:
    (blocks a chunk, chunks a full table holds, items an iteration).
    A chunk is a whole number of blocks, so C = blocks * block_size
    slots; T items of C slots are gathered and attended at a time."""
    block_bytes = block_size * n_kv * hd * 4     # attended as float32
    nb_c = max(1, min(max_blocks, CHUNK_BYTES // block_bytes))
    n_chunks = -(-max_blocks // nb_c)
    t = max(1, min(b * n_chunks, ITER_BYTES // (nb_c * block_bytes)))
    return nb_c, n_chunks, t


def walk_slots(pos, block_size: int, nb_c: int, t: int) -> int:
    """Pool slots one layer of a decode step gathers for the bucket's
    positions `pos` (padding rows included) under a plan of `nb_c` blocks
    a chunk and `t` items an iteration: whole iterations of T chunks of
    C slots. Host arithmetic, for the families' counters."""
    c = nb_c * block_size
    items = sum(int(p) // c + 1 for p in pos)
    return -(-items // t) * t * c


def live_items(tables, pos, block_size, nb_c, n_chunks, t, lo=None):
    """The step's work list, made on the device from `pos` and
    `tables`: item i is one chunk of one row's live blocks, rows in
    order, row r holding pos[r] // C + 1 of them. Returns per item its
    row, its pool blocks (nb_c,), the last live slot inside its chunk
    (-1 for the items past the total, which read the scratch block and
    count for nothing) and the number of T-item iterations.

    `lo` (B,), where given, is each row's first live position (a window's
    lower edge): row r then holds the chunks from lo[r] // C on, and a
    fifth value is returned, the first live slot inside each item's
    chunk (at or below 0: the whole chunk is behind the edge)."""
    b, max_blocks = tables.shape
    c = nb_c * block_size
    n_items = -(-(b * n_chunks) // t) * t
    chunks = pos // c + 1                                    # (B,)
    if lo is not None:
        chunk0 = lo // c
        chunks = chunks - chunk0
    ends = jnp.cumsum(chunks)
    i = jnp.arange(n_items, dtype=pos.dtype)
    valid = i < ends[-1]
    row = jnp.minimum(
        jnp.sum(i[:, None] >= ends[None, :], axis=1), b - 1)
    chunk = jnp.where(valid, i - (ends - chunks)[row], 0)
    if lo is not None:
        chunk = chunk + jnp.where(valid, chunk0[row], 0)
    # a table's tail past max_blocks, like an item past the total,
    # reads block 0: the scratch block
    tab = jnp.pad(tables, ((0, 0), (0, n_chunks * nb_c - max_blocks)))
    blocks = tab[row[:, None], chunk[:, None] * nb_c + jnp.arange(nb_c)]
    blocks = jnp.where(valid[:, None], blocks, 0)
    last = jnp.where(valid, pos[row] - chunk * c, -1)
    n_iter = (ends[-1] + t - 1) // t
    if lo is None:
        return row, blocks, last, n_iter
    return row, blocks, last, n_iter, lo[row] - chunk * c


# -- exact selection without a sort -------------------------------------------

def sort_keys(x):
    """f32 -> uint32 with the same order (and -0.0 == +0.0: a tie, as
    in `sparse_moe.select_rows`). Every real number's key is above 0,
    which is kept for slots a query may not attend."""
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
    how many each query takes (no more than it may attend). Returns (T
    (C,) uint32, P (C,) int32): query c takes the slots with ``key >
    T[c]``, and those with ``key == T[c]`` at positions ``<= P[c]``: the
    k_eff slots of largest key, ties to the lower position."""
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


# -- a chunk's walk over its context tiles ------------------------------------

# Context slots one iteration of a chunk program's walks covers (a whole
# number of blocks). The plain attention update keeps a tile's (heads, C,
# tile) f32 scores in memory, 268 MB at 32 heads and C 2048; the fused one
# keeps a block of them in fast memory, and the sparse-expert program's
# largest temporary is then its (C, max_len) integer keys.
CTX_TILE = 1024

# Queries a program of the selected tile update takes at once, against a
# whole tile (`pallas_ops.selected_block_update`; read on the chip,
# PERF.md PR 32).
FUSED_Q_BLOCK = 128


def fused_attend(c: int, tile: int, hd: int) -> bool:
    """Whether a chunk of `c` queries walks its context tiles of `tile`
    slots with a fused update (`pallas_ops.selected_block_update`,
    `pallas_ops.causal_block_update`) or a plain one (`attend_plain`,
    `attend_tile_plain`): from the backend and the shapes alone. The
    kernels take a KV head as a lane tile (hd and the tile multiples of
    128) and whole blocks of queries."""
    return (jax.default_backend() == "tpu" and hd % 128 == 0
            and tile % 128 == 0 and c % min(FUSED_Q_BLOCK, c) == 0)


def tile_span(pos0, c: int, slots: int, tile: int, window: int = 0):
    """(first, end) of the context tiles of `tile` slots a chunk of `c`
    queries at `pos0` walks under a table of `slots` slots: up to the
    chunk's own last tile, and on a window layer (`window` > 0) from the
    tile that holds the first query's window floor. Every chunk walk's
    trip count, in arithmetic that the host's ints (a family's counters)
    and a program's traced `pos0` both take."""
    n, cap = -(-(pos0 + c) // tile), -(-slots // tile)
    end = n - (n > cap) * (n - cap)
    if not window:
        return 0 * end, end
    lo = pos0 - (window - 1)
    return (lo > 0) * (lo // tile), end


def whole_tiles(table, tile: int, block_size: int):
    """A sequence's table (MB,) filled up to whole context tiles of
    `tile` slots, which `block_size` has to divide; the tail past
    max_blocks reads block 0: the scratch block."""
    if tile % block_size:
        raise ValueError(f"block_size {block_size} does not divide the "
                         f"context tile of {tile} slots")
    nb_t = tile // block_size
    max_tiles = -(-table.shape[0] // nb_t)
    return jnp.pad(table, (0, max_tiles * nb_t - table.shape[0]))


def walk_tiles(tab, span, nb_t: int, read, update, heads, c: int, vw: int,
               l_floor=None):
    """One walk of a chunk's attention over the context tiles `span`
    (first, end; traced or not) of the table `tab` (`whole_tiles`), `nb_t`
    blocks a tile: ``read(blocks)`` gives a tile's keys and values as the
    family keeps them, ``update(j, kt, vt, state)`` the online softmax's
    carry after tile j: (m, l, acc) in f32, `heads` + (c,) and `heads` +
    (c, vw) for `c` queries and values `vw` wide. Returns ``acc / l``, or
    under `l_floor` ``acc / max(l, l_floor)``: for a walk some of whose
    queries (padding past the table's last tile) attend nothing."""
    def one(j, state):
        bl = jax.lax.dynamic_slice_in_dim(tab, j * nb_t, nb_t)
        return update(j, *read(bl), state)

    _, l, acc = jax.lax.fori_loop(*span, one, (
        jnp.full(heads + (c,), -1e30, _F32), jnp.zeros(heads + (c,), _F32),
        jnp.zeros(heads + (c, vw), _F32)))
    if l_floor is not None:
        l = jnp.maximum(l, l_floor)
    return acc / l[..., None]


def attend_plain(qg, kt, vt, key_t, t, cut, first, state):
    """One context tile of a chunk's attention walk in plain XLA: qg
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


def attend_tile_plain(qg, kt, vt, qpos, first, window: int, state):
    """One context tile in plain XLA (`attend_plain`) under the causal
    edge and, where `window` > 0, the window's: the queries at positions
    qpos (C,) against the slots from `first` on, the mask as selection
    keys of 1 and 0 under a threshold of 0 with no tie taken."""
    c = qpos.shape[0]
    s = (first + jnp.arange(kt.shape[0]))[None, :]
    on = s <= qpos[:, None]
    if window:
        on = on & (s > qpos[:, None] - window)
    return attend_plain(
        qg, kt, vt, on.astype(jnp.uint32), jnp.zeros((c,), jnp.uint32),
        jnp.full((c,), -1, jnp.int32), 0, state)


def causal_update(qg, qh, kt, vt, qpos, first, state, *, fused: bool,
                  window: int = 0):
    """One context tile's update under the causal edge and a window, for
    queries at the consecutive positions qpos (C,): in one kernel where
    `fused` (`pallas_ops.causal_block_update`, the mask made inside from
    qpos[0]; qh (Hkv, G, C, hd) its layout of qg), else `attend_tile_plain`."""
    if fused:
        from nnstreamer_tpu.backends import pallas_ops

        return pallas_ops.causal_block_update(
            qh, kt, vt, qpos[0], first, *state, window=window)
    return attend_tile_plain(qg, kt, vt, qpos, first, window, state)
