"""Pallas paged attention: the flash kernels taught block tables.

The serving gap this closes (bench round r05): the
continuous-batching LLM path computed attention as plain-XLA block-table
gathers + full einsums + ``-1e30``-mask softmax (`llm/paged_model.py`),
materializing the whole ``(B, max_blocks*block_size, n_kv, hd)``
gathered cache every decode step, while the repo's own flash kernel
(`pallas_ops._flash_kernel`) measured 9.2x (s2048) to 165x (s8192) over
XLA attention. These kernels keep the flash formulation — online
softmax, K/V streamed through VMEM one block at a time — but fetch each
K/V block through the *per-sequence block table* with
``PrefetchScalarGridSpec`` scalar prefetch, so the block-table
indirection happens in the BlockSpec index map (a DMA address
computation), never as a gather materialized in HBM.

Two kernels:

- ``paged_decode_attn`` — one query token per sequence row, grid
  ``(batch, table_blocks)``: program ``(b, j)`` streams pool block
  ``table[b, j]`` through VMEM, carrying the online-softmax state
  ``(m, l, acc)`` in VMEM scratch across the sequential ``j`` steps.
  Rows mask inclusively at ``kv_pos <= pos[b]`` — identical semantics
  to ``paged_decode_step``'s mask, so stale/unwritten slots contribute
  exactly nothing. Blocks entirely past ``pos[b]`` are skipped
  (``pl.when``), so a shallow sequence in a deep batch does not pay for
  the deep one's table length.
- ``paged_prefill_attn`` — causal q-blocked prefill over the pool,
  grid ``(heads, q_blocks, table_blocks)``: the chunk's queries attend
  every pool block the table maps below their absolute positions
  (earlier chunks' KV included), masking ``q_pos >= k_pos`` from global
  offsets. GQA is resolved in the index map (head ``h`` fetches KV head
  ``h // group``), so the narrow KV pool is never group-expanded in
  memory.

On top of them, drop-in twins of the XLA reference functions
(``paged_flash_decode_step`` / ``paged_flash_prefill_chunk``) run the
full layer stack with the same pool-scatter writes and quant-aware
projections; `backends/llm_exec.py` selects between the two families
via the ``paged_kernel`` knob with the XLA path as the bit-reference
(tests/test_paged_kernels.py pins ≤1e-5 logits parity in interpret
mode). The KV scatter itself stays an XLA ``.at[].set`` — scatter is a
gather/scatter-unit op, not a Pallas sweet spot (see
``pallas_ops.sparse_to_dense``); the kernels read the pool *after* the
step's writes land, which inside one jit is just a data dependence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.backends.pallas_ops import (
    _interpret, _online_softmax_update)


# -- paged flash decode ------------------------------------------------------

def _paged_decode_kernel(scale: float, bs: int, n_kv: int, n_heads: int,
                         tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr):
    """One (row, table-block) program. The row's online-softmax carry
    (m, l, acc) lives in VMEM scratch, persisting across the sequential
    innermost grid dim; GQA runs as a static loop over KV heads, each
    group reusing `_online_softmax_update` so the mask/normalizer
    semantics are shared with every flash kernel in pallas_ops."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_b = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos_b = pos_ref[b]

    # table blocks entirely past this row's write position hold no
    # attended slots — skip the whole program (per-row early exit)
    @pl.when((j * bs) <= pos_b)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (n_heads, hd)
        k_blk = k_ref[0].astype(jnp.float32)        # (bs, n_kv, hd)
        v_blk = v_ref[0].astype(jnp.float32)
        kvpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = kvpos <= pos_b                      # (1, bs) inclusive
        g = n_heads // n_kv
        m = m_scr[0]
        l = l_scr[0]
        acc = acc_scr[...]
        ms, ls, accs = [], [], []
        for kv in range(n_kv):                      # static GQA groups
            sl = slice(kv * g, (kv + 1) * g)
            mask = jnp.broadcast_to(valid, (g, bs))
            m_g, l_g, acc_g = _online_softmax_update(
                q[sl], k_blk[:, kv, :], v_blk[:, kv, :],
                m[sl], l[sl], acc[sl], scale, mask)
            ms.append(m_g)
            ls.append(l_g)
            accs.append(acc_g)
        m = jnp.concatenate(ms)
        l = jnp.concatenate(ls)
        acc = jnp.concatenate(accs, axis=0)
        m_scr[...] = jnp.broadcast_to(m[None, :], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l[None, :], l_scr.shape)
        acc_scr[...] = acc

    @pl.when(j == n_b - 1)
    def _finalize():
        l = jnp.maximum(l_scr[0], 1e-20)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attn(q, k_pool_l, v_pool_l, tables, pos):
    """Paged flash attention for one decode step of one layer.

    q (B, n_heads, hd) — the step's rope'd queries; k/v_pool_l
    (num_blocks, block_size, n_kv, hd) — ONE layer's pool, already
    holding this step's K/V writes; tables (B, max_blocks) int32;
    pos (B,) int32 per-row positions. Returns (B, n_heads, hd) f32.

    The per-row block table rides scalar prefetch: the K/V BlockSpec
    index map reads ``tables[b, j]`` to address pool block DMAs, so the
    full gathered cache never exists — per-program VMEM is one
    (block_size, n_kv, hd) block.
    """
    b, n_heads, hd = q.shape
    _, bs, n_kv, _ = k_pool_l.shape
    mb = tables.shape[1]
    scale = hd ** -0.5
    kern = functools.partial(_paged_decode_kernel, scale, bs, n_kv,
                             n_heads)
    row = pl.BlockSpec((1, n_heads, hd), lambda i, j, tab, pos: (i, 0, 0))
    blk = pl.BlockSpec(
        (1, bs, n_kv, hd),
        lambda i, j, tab, pos: (tab[i * mb + j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[row, blk, blk],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((8, n_heads), jnp.float32),   # m (sublane-repl)
            pltpu.VMEM((8, n_heads), jnp.float32),   # l
            pltpu.VMEM((n_heads, hd), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), jnp.float32),
        interpret=_interpret(),
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      q, k_pool_l, v_pool_l)


# -- paged flash prefill -----------------------------------------------------

def _paged_prefill_kernel(scale: float, bs: int, bq: int,
                          tab_ref, p0_ref, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr):
    """One (head, q-block, table-block) program: causal flash update of
    bq chunk queries against pool block ``table[j]``. Global query
    positions are ``p0 + qi*bq + row`` (p0 = the chunk's absolute start,
    scalar-prefetched), key positions ``j*bs + col`` — the same
    rows>=cols mask geometry as `pallas_ops._causal_mask`, shifted by
    the chunk offset so later chunks attend earlier chunks' pool KV."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p0 = p0_ref[0]
    q_lo = p0 + i * bq

    # blocks entirely above this q-block's last row are fully masked
    @pl.when((j * bs) <= (q_lo + bq - 1))
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k_blk = k_ref[0].astype(jnp.float32)        # (bs, hd)
        v_blk = v_ref[0].astype(jnp.float32)
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        m, l, acc = _online_softmax_update(
            q, k_blk, v_blk, m_scr[0], l_scr[0], acc_scr[...], scale,
            rows >= cols)
        m_scr[...] = jnp.broadcast_to(m[None, :], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l[None, :], l_scr.shape)
        acc_scr[...] = acc

    @pl.when(j == n_j - 1)
    def _finalize():
        l = jnp.maximum(l_scr[0], 1e-20)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _auto_bq(s: int, want: int = 128) -> int:
    b = min(want, s)
    while b > 8 and s % b:
        b //= 2
    return b


def paged_prefill_attn(q, k_pool_l, v_pool_l, table, pos0):
    """Causal paged flash attention for one prefill chunk of one layer.

    q (n_heads, S_c, hd) — the chunk's rope'd queries (S_c = the padded
    chunk bucket); k/v_pool_l (num_blocks, block_size, n_kv, hd) — one
    layer's pool with the chunk's K/V already scattered in; table
    (max_blocks,) int32 — the sequence's block table; pos0 — the
    chunk's absolute start position (traced scalar). Returns
    (n_heads, S_c, hd) f32.

    Head ``h`` fetches KV head ``h // group`` straight from the narrow
    pool in its index map — GQA without a group-expanded copy. The pool
    is viewed as (num_blocks, block_size, n_kv*hd) so that one KV head
    of one block is a (block_size, hd) tile on the lane axis: Mosaic
    takes a block whose last two dims are (8k, 128k) or the whole
    array's, and a (1, hd) slice of (n_kv, hd) is neither. With
    n_kv > 1 that needs hd % 128 == 0.
    """
    n_heads, s_c, hd = q.shape
    nb, bs, n_kv, _ = k_pool_l.shape
    mb = table.shape[0]
    g = n_heads // n_kv
    bq = _auto_bq(s_c)
    scale = hd ** -0.5
    kern = functools.partial(_paged_prefill_kernel, scale, bs, bq)
    kv_blk = pl.BlockSpec(
        (1, bs, hd), lambda h, i, j, tab, p0: (tab[j], 0, h // g))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_heads, s_c // bq, mb),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j, tab, p0: (h, i, 0)),
            kv_blk, kv_blk,
        ],
        out_specs=pl.BlockSpec((1, bq, hd),
                               lambda h, i, j, tab, p0: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, bq), jnp.float32),
            pltpu.VMEM((8, bq), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_heads, s_c, hd), jnp.float32),
        interpret=_interpret(),
    )(table.astype(jnp.int32), jnp.asarray(pos0, jnp.int32).reshape(1),
      q, k_pool_l.reshape(nb, bs, n_kv * hd),
      v_pool_l.reshape(nb, bs, n_kv * hd))


# -- latent decode: a row's whole context in one program ---------------------

def _token_rows(ref, rope: int):
    """The packed rows of roped keys in `ref` (S // 2, 2 * rope), tokens
    2r and 2r + 1 side by side, as a row a token (S, 2 * rope) in slot
    order: a token's key in the first `rope` values, its neighbour's
    after it (which the queries meet with zeros). Row 2r is row r, row
    2r + 1 row r with its halves exchanged. Four-byte values interleave
    through two strided stores; bfloat16 in the 32-bit words fast memory
    keeps two rows in, row 2r the low half and row 2r + 1 the high one,
    each half a float32's upper bits."""
    half, w = ref.shape
    if ref.dtype.itemsize == 4:
        def interleave(rows):
            rows[pl.ds(0, half, stride=2), :] = ref[...]
            rows[pl.ds(1, half, stride=2), :] = pltpu.roll(ref[...], rope, 1)
            return rows[...]

        return pl.run_scoped(interleave, pltpu.VMEM((2 * half, w), ref.dtype))
    bits = jax.lax.bitcast_convert_type(
        ref[...].astype(jnp.float32), jnp.uint32)
    return pltpu.bitcast((bits >> 16) | pltpu.roll(bits, rope, 1), ref.dtype)


def _latent_decode_kernel(scale: float, bs: int, nb: int, mb: int,
                          meta_ref, tab_ref, pos_ref, q_ref, k_hbm, i_hbm,
                          o_ref, k_buf, i_buf, sem, turn, m_scr, l_scr,
                          acc_scr):
    """One row of the bucket, its whole context: `nb` blocks a step,
    copied through the row's table from the pools in HBM into one of two
    buffers while the step before is attended. A row's first step is
    started by the row before it, so the copies run ahead across rows;
    `turn` holds the buffer the next step waits on. The carry (m, l, acc)
    stays in fast memory for the row and o_ref is written once, at its
    end. Rows past the live ones copy nothing and write zeros. q_ref (1,
    H, rank + 2 rope) = (q_lat | q_pe | 0): `_token_rows` says why."""
    b = pl.program_id(0)
    li, n_live = meta_ref[0], meta_ref[1]
    rank = k_buf.shape[-1]
    half, step = bs // 2, nb * bs

    def copies(blk, k, buf):
        """Pool block `blk` to place k of buffer `buf`: its latents and
        its packed roped keys, both on the buffer's semaphore."""
        return (pltpu.make_async_copy(
            k_hbm.at[li, blk],
            k_buf.at[buf, pl.ds(pl.multiple_of(k * bs, bs), bs)],
            sem.at[buf]),
                pltpu.make_async_copy(
            i_hbm.at[li, blk],
            i_buf.at[buf, pl.ds(pl.multiple_of(k * half, half), half)],
            sem.at[buf]))

    def start(row, j, buf):
        """Step j of `row` on its way: block k of it through the table,
        the index held at the row's last live block (a step's tail past
        it reads that block again, under the mask). A loop, not `nb`
        copies written out: those read 8 % faster alone on the chip (0.66
        against 0.73 ms a layer of the DeepSeek-V2 cell) and cost every
        run's set-up 10 s, the kernel being traced and lowered for each
        kind of layer of each decode bucket (PERF.md, PR 43)."""
        last = pos_ref[row] // bs

        def one(k, _):
            blk = tab_ref[row * mb + jnp.minimum(j * nb + k, last)]
            for c in copies(blk, k, buf):
                c.start()

        jax.lax.fori_loop(0, nb, one, None)

    def wait(buf):
        def one(k, _):
            for c in copies(0, k, buf):
                c.wait()

        jax.lax.fori_loop(0, nb, one, None)

    @pl.when((b == 0) & (n_live > 0))
    def _first():
        turn[0] = 0
        start(0, 0, 0)

    @pl.when(b < n_live)
    def _row():
        pos = pos_ref[b]
        n_j = pos // step + 1
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q_lat, q_pe = q_ref[0, :, :rank], q_ref[0, :, rank:]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, step), 1)

        def dot(x, y, dims):
            return jax.lax.dot_general(x, y, (dims, ((), ())),
                                       preferred_element_type=jnp.float32)

        def attend(j, _):
            buf = turn[0]
            more = j + 1 < n_j

            @pl.when(more | (b + 1 < n_live))
            def _ahead():
                start(jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0),
                      1 - buf)

            wait(buf)
            lat = k_buf[buf].reshape(step, rank)
            pe = _token_rows(i_buf.at[buf], q_pe.shape[1] // 2)
            ok = j * step + slot <= pos
            s = jnp.where(ok, (dot(q_lat, lat, ((1,), (1,)))
                               + dot(q_pe, pe, ((1,), (1,)))) * scale, -1e30)
            m = m_scr[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            old = jnp.exp(m - m_new)
            l_scr[...] = l_scr[...] * old + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * old + dot(
                p.astype(lat.dtype), lat, ((1,), (0,)))
            m_scr[...] = m_new
            turn[0] = 1 - buf

        jax.lax.fori_loop(0, n_j, attend, None)
        o_ref[0] = acc_scr[...] / l_scr[...]

    @pl.when(b >= n_live)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)


def latent_decode_attn(q, k_pool, i_pool, li, tables, pos, n_live, *,
                       scale: float, step: int, interpret=None):
    """Layer `li`'s decode attention of the latent family
    (llm/latent_moe.py) in the latent, one program a row of the bucket.

    q (B, H, rank + rope): the step's absorbed queries; k_pool (layers,
    blocks, block_size, 1, rank): the latents, and i_pool (layers, blocks,
    block_size // 2, 2 * rope): the roped keys, two tokens a row, both
    whole and already holding the step's writes (`li` is traced: a
    ``k_pool[li]`` in front of the call would slice a layer out);
    tables (B, max_blocks) int32; pos (B,) int32; n_live () int32, the
    real rows (the first ones). Row b attends its slots ``<= pos[b]`` in
    steps of `step` slots (whole blocks). Returns the heads' sums of
    latents (B, H, rank) f32, zeros for the rows past `n_live`.

    The table, the positions, `li` and `n_live` ride scalar prefetch and
    the pools stay in HBM: the kernel copies a step's blocks itself, two
    buffers deep, so no gathered copy of the context exists, a step past a
    row's context or a row past the live ones moves nothing, and the
    online-softmax carry never leaves fast memory (no merge across rows).
    Scores, softmax and sums in float32, the probabilities cast to the
    pool's type for the sums, `scale` applied to the float32 scores:
    `latent_moe.attend_latent`'s arithmetic, a row's steps added in order.
    """
    b, nh, qw = q.shape
    nl, nblk, bs, _, rank = k_pool.shape
    w = i_pool.shape[3]
    rope = qw - rank
    if i_pool.shape[2] * 2 != bs or w != 2 * rope or step % bs:
        raise ValueError(
            f"latent_decode_attn: roped keys {i_pool.shape} are not two "
            f"tokens a row of blocks of {bs} slots, or a step of {step} "
            f"slots is not whole blocks")
    mb = tables.shape[1]
    # each pool is handed over as XLA:TPU lays it out: 16-bit latents with
    # their unit axis set aside (the view without it is the same bytes),
    # 32-bit ones a row a tile (that view would copy the pool)
    unit = (1,) * (k_pool.dtype.itemsize == 4)
    # a token's row of roped keys holds its neighbour's after its own
    q3 = jnp.pad(q, ((0, 0), (0, 0), (0, rope)))
    kern = functools.partial(_latent_decode_kernel, float(scale), bs,
                             step // bs, mb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nh, rank + w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nh, rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, step) + unit + (rank,), k_pool.dtype),
            pltpu.VMEM((2, step // 2, w), i_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),             # the buffer in turn
            pltpu.VMEM((nh, 1), jnp.float32),        # m
            pltpu.VMEM((nh, 1), jnp.float32),        # l
            pltpu.VMEM((nh, rank), jnp.float32),     # acc
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, rank), jnp.float32),
        name="latent_decode_attn",
        # a row's first copies are started by the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.stack([li, n_live]).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32), q3,
      k_pool.reshape((nl, nblk, bs) + unit + (rank,)), i_pool)


# -- full layer-stack twins (jitted by llm_exec) -----------------------------

def paged_flash_decode_step(params, cur, tables, pos, k_pool, v_pool,
                            *, n_heads=4, dtype=jnp.float32):
    """Drop-in twin of `paged_model.paged_decode_step` with the
    attention einsums replaced by `paged_decode_attn`. Everything else
    — rope, pool write-through, residual/MLP structure, quant-aware
    projections — is shared with the reference via `llm/parts.py`, so the two paths can only diverge in the attention kernel
    itself (the thing the parity tests pin)."""
    from nnstreamer_tpu.llm.parts import mlp_paged, proj, rope_rows
    from nnstreamer_tpu.models.transformer import rmsnorm

    b = cur.shape[0]
    block_size = k_pool.shape[2]
    rows = jnp.arange(b)
    write_blk = tables[rows, pos // block_size]
    write_off = pos % block_size
    x = params["embed"][cur][:, None, :].astype(dtype)
    for li, blk in enumerate(params["blocks"]):
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        d = x.shape[-1]
        hd = d // n_heads
        qkv = proj(blk, "wqkv", h, dtype)
        kv_dim = (qkv.shape[-1] - d) // 2
        n_kv = kv_dim // hd
        q = qkv[..., :d].reshape(b, 1, n_heads, hd)
        k = qkv[..., d:d + kv_dim].reshape(b, 1, n_kv, hd)
        v = qkv[..., d + kv_dim:].reshape(b, 1, n_kv, hd)
        q, k = rope_rows(q, pos), rope_rows(k, pos)
        k_pool = k_pool.at[li, write_blk, write_off].set(
            k[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[li, write_blk, write_off].set(
            v[:, 0].astype(v_pool.dtype))
        attn = paged_decode_attn(q[:, 0], k_pool[li], v_pool[li],
                                 tables, pos)
        x = x + proj(blk, "wo", attn.reshape(b, 1, -1).astype(dtype),
                      dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + mlp_paged(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = proj(params, "head", x[:, 0], dtype).astype(jnp.float32)
    return logits, k_pool, v_pool


def paged_flash_prefill_chunk(params, ids, pos0, blk_idx, blk_off,
                              table, k_pool, v_pool, last_idx,
                              *, n_heads=4, dtype=jnp.float32):
    """Drop-in twin of `paged_model.paged_prefill_chunk` with the
    attention gather+einsum replaced by `paged_prefill_attn`: the chunk
    writes its K/V into the pool and attends the whole prefix (earlier
    chunks included) straight through the block table, one pool block
    per DMA."""
    from nnstreamer_tpu.llm.parts import mlp_paged, proj
    from nnstreamer_tpu.models.transformer import rmsnorm, rope

    _, c = ids.shape
    x = params["embed"][ids].astype(dtype)            # (1, C, D)
    pos = pos0 + jnp.arange(c)
    for li, blk in enumerate(params["blocks"]):
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        d = x.shape[-1]
        hd = d // n_heads
        qkv = proj(blk, "wqkv", h, dtype)
        kv_dim = (qkv.shape[-1] - d) // 2
        n_kv = kv_dim // hd
        q = qkv[..., :d].reshape(1, c, n_heads, hd)
        k = qkv[..., d:d + kv_dim].reshape(1, c, n_kv, hd)
        v = qkv[..., d + kv_dim:].reshape(1, c, n_kv, hd)
        q, k = rope(q, pos), rope(k, pos)
        k_pool = k_pool.at[li, blk_idx, blk_off].set(
            k[0].astype(k_pool.dtype))
        v_pool = v_pool.at[li, blk_idx, blk_off].set(
            v[0].astype(v_pool.dtype))
        attn = paged_prefill_attn(q[0].transpose(1, 0, 2), k_pool[li],
                                  v_pool[li], table, pos0)
        attn = attn.transpose(1, 0, 2).reshape(1, c, -1).astype(dtype)
        x = x + proj(blk, "wo", attn, dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + mlp_paged(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = proj(params, "head", x[0, last_idx][None, :],
                   dtype).astype(jnp.float32)
    return logits[0], k_pool, v_pool
