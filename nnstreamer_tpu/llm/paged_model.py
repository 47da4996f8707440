"""Paged prefill / decode-step math (pure jax, jitted by llm_exec).

Parity contract (the acceptance gate): at temperature 0 the paged
engine's tokens must equal `transformer.generate`'s token-for-token.
The functions here keep `transformer._step_impl`'s cached attention —
f32 scores, the inclusive window `slot <= pos`, softmax in f32 — over
KV read through block tables instead of a contiguous ring.

Which slots are read. The prefill functions gather a table's whole
`max_blocks * block_size` slots behind a -1e30 additive mask. The decode
step reads each row's *live* blocks and no others: row r holds
`pos[r] // C + 1` chunks of C slots (C a whole number of blocks), the
chunks of all rows form one work list made on the device from `pos` and
`tables`, and a loop whose trip count is a value of that list (not a
shape: one program per decode bucket, whatever the contexts) attends T
chunks at a time and merges them into the rows' online-softmax state
(m, l, acc), f32, as the flash kernels do. Slots past `pos[r]` inside a
row's last chunk, and the scratch block that the list's tail past its
total points at, are read and masked.

Why masked and unread slots give the same bits of nothing. A masked
slot's score is replaced by -1e30 before the maximum and its weight by
0.0 after the exponential, and 0.0 times any finite stale value is 0.0
in the value contraction; a chunk that holds no live slot of its row is
never read, which adds the same 0.0 to the same sums. An item past the
total has no owner in the (T, B) relation that merges items into rows,
so its zeros reach no row, and a padding row's chunk (pos 0, a table of
scratch) reaches its own row only, whose logits the host slices off.
What differs from the dense softmax is the order of the f32 sums (per
chunk, then across chunks, rescaled by exp(m_chunk - m_row)), as it
already does between this path and the Pallas twin (parity 1e-5).

Shapes:
- k/v pool: (L, num_blocks, block_size, n_kv, hd)  — PagedKVCache
- prefill:  ids (1, S_b) padded prompt; per-position (block, offset)
  scatter targets (padding targets the scratch block)
- decode:   one token per sequence row; per-row block tables
  (B_b, max_blocks) and positions (B_b,) (padding rows → scratch)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.llm.parts import mlp_paged, proj, rope_rows
from nnstreamer_tpu.models.transformer import (
    expand_kv, apply_seq_kv, rmsnorm)


def paged_prefill(params, ids, blk_idx, blk_off, k_pool, v_pool, last_idx,
                  *, n_heads=4, dtype=jnp.float32):
    """Bucketed prompt prefill: full-sequence forward + KV scatter.

    ids (1, S_b) int32 — the prompt padded to its pow2 bucket;
    blk_idx/blk_off (S_b,) int32 — per-position pool write targets
    (padding positions point at the scratch block); last_idx — index of
    the final real prompt token. Returns (last-token logits (vocab,),
    k_pool, v_pool). Pools are donated by the caller's jit.
    """
    logits, ks, vs = apply_seq_kv(params, ids, n_heads=n_heads,
                                  dtype=dtype)
    # ks/vs: (L, 1, S_b, n_kv, hd) → scatter each position into its
    # (block, offset) slot across all layers at once
    k_pool = k_pool.at[:, blk_idx, blk_off].set(
        ks[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[:, blk_idx, blk_off].set(
        vs[:, 0].astype(v_pool.dtype))
    return logits[0, last_idx], k_pool, v_pool


def _attend_live(q, k_pool, v_pool, li, items, t, n_heads):
    """Layer `li`'s attention of q (B, H, hd) f32 over each row's live
    slots: a loop over the work list, T items at a time, with the
    online-softmax state (m, l, acc) per row and head in f32. Several
    items of one iteration may belong to one row; they merge through
    the (T, B) relation `own`."""
    row, blocks, last, n_iter = items
    b, _, hd = q.shape
    block_size, n_kv = k_pool.shape[2], k_pool.shape[3]
    c = blocks.shape[1] * block_size
    hi = jax.lax.Precision.HIGHEST

    def body(j, state):
        m, l, acc = state
        r = jax.lax.dynamic_slice_in_dim(row, j * t, t)
        bl = jax.lax.dynamic_slice_in_dim(blocks, j * t, t)
        la = jax.lax.dynamic_slice_in_dim(last, j * t, t)
        kc = k_pool[li, bl].reshape(t, c, n_kv, hd)
        vc = v_pool[li, bl].reshape(t, c, n_kv, hd)
        kcx = expand_kv(kc, n_heads).astype(jnp.float32)
        s = jnp.einsum("thd,tchd->thc", q[r], kcx) * hd ** -0.5
        # the same inclusive window as _step_impl's `<= p`
        live = (jnp.arange(c)[None, :] <= la[:, None])[:, None, :]
        s = jnp.where(live, s, -1e30)
        mi = jnp.max(s, axis=-1)                             # (T, H)
        p = jnp.where(live, jnp.exp(s - mi[..., None]), 0.0)
        vcx = expand_kv(vc, n_heads).astype(jnp.float32)
        ai = jnp.einsum("thc,tchd->thd", p, vcx)
        own = (r[:, None] == jnp.arange(b)[None, :]) & (la >= 0)[:, None]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(own[:, :, None], mi[:, None, :], -1e30), axis=0))
        w = jnp.exp(mi - m_new[r])                           # (T, H)
        old = jnp.exp(m - m_new)
        ownf = own.astype(jnp.float32)
        l = l * old + jnp.einsum(
            "tb,th->bh", ownf, jnp.sum(p, axis=-1) * w, precision=hi)
        acc = acc * old[..., None] + jnp.einsum(
            "tb,thd->bhd", ownf, ai * w[..., None], precision=hi)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (
        jnp.full((b, n_heads), -1e30, jnp.float32),
        jnp.zeros((b, n_heads), jnp.float32),
        jnp.zeros((b, n_heads, hd), jnp.float32)))
    return acc / l[..., None]


@functools.partial(jax.jit, static_argnames=("t", "n_heads", "dtype"))
def _decode_layer(blk, x, li, pos, write_blk, write_off, items,
                  k_pool, v_pool, *, t, n_heads, dtype):
    """Layer `li` of a decode step: write the step's K/V to the pool,
    attend the live context, MLP. Jitted with `li` an argument, so a
    step traces and lowers one layer, not one per layer of the model
    (XLA inlines the calls: the compiled program is the same)."""
    b, _, d = x.shape
    n_kv, hd = k_pool.shape[3], k_pool.shape[4]
    kv_dim = n_kv * hd
    h = rmsnorm(x, blk["ln1"].astype(dtype))
    qkv = proj(blk, "wqkv", h, dtype)
    q = qkv[..., :d].reshape(b, 1, n_heads, hd)
    k = qkv[..., d:d + kv_dim].reshape(b, 1, n_kv, hd)
    v = qkv[..., d + kv_dim:].reshape(b, 1, n_kv, hd)
    q, k = rope_rows(q, pos), rope_rows(k, pos)
    k_pool = k_pool.at[li, write_blk, write_off].set(
        k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[li, write_blk, write_off].set(
        v[:, 0].astype(v_pool.dtype))
    attn = _attend_live(q[:, 0].astype(jnp.float32), k_pool, v_pool,
                        li, items, t, n_heads).astype(dtype)
    x = x + proj(blk, "wo", attn.reshape(b, 1, -1), dtype)
    h = rmsnorm(x, blk["ln2"].astype(dtype))
    return x + mlp_paged(blk, h, dtype), k_pool, v_pool


def paged_decode_step(params, cur, tables, pos, k_pool, v_pool,
                      *, n_heads=4, dtype=jnp.float32):
    """One decode step for a bucketed batch over the paged pool.

    cur (B_b,) int32 current tokens; tables (B_b, max_blocks) int32
    per-sequence block tables; pos (B_b,) int32 write positions.
    Returns (logits (B_b, vocab) f32, k_pool, v_pool).

    Mirrors `transformer._step_impl` with three serving deltas: the
    cache axis is read through the block tables, each row's live
    blocks only; positions are per-row (sequences at different depths
    share one step), and there is no ring wrap — admission enforces
    prompt+new <= table capacity.
    """
    b = cur.shape[0]
    _, _, block_size, n_kv, hd = k_pool.shape
    nb_c, n_chunks, t = parts.walk_plan(block_size, n_kv, hd, b,
                                   tables.shape[1])
    write_blk = tables[jnp.arange(b), pos // block_size]      # (B,)
    write_off = pos % block_size
    items = parts.live_items(tables, pos, block_size, nb_c, n_chunks, t)
    x = params["embed"][cur][:, None, :].astype(dtype)   # (B,1,D)
    for li, blk in enumerate(params["blocks"]):
        x, k_pool, v_pool = _decode_layer(
            blk, x, li, pos, write_blk, write_off, items, k_pool, v_pool,
            t=t, n_heads=n_heads, dtype=dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = proj(params, "head", x[:, 0], dtype).astype(jnp.float32)
    return logits, k_pool, v_pool


def paged_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table,
                        k_pool, v_pool, last_idx,
                        *, n_heads=4, dtype=jnp.float32):
    """One prompt chunk for a single sequence — the XLA reference for
    chunked prefill.

    ids (1, C_b) int32 — this chunk's tokens padded to the chunk
    bucket; pos0 () int32 — absolute position of the chunk's first
    token; blk_idx/blk_off (C_b,) int32 — pool write targets for each
    chunk position (padding → scratch block); table (max_blocks,)
    int32 — the sequence's full block table, through which attention
    reads everything written so far *including this chunk's own
    scatter*; last_idx — index of the final real token in this chunk.

    Causality is positional: query at absolute position p attends to
    pool slots holding absolute positions <= p. Earlier chunks live in
    the pool already (written by previous chunk calls); later slots are
    masked off by the position comparison, so chunked == unchunked up
    to float reassociation.

    Returns (last-token logits (vocab,) f32, k_pool, v_pool).
    """
    c = ids.shape[1]
    n_layers, _, block_size, _, _ = k_pool.shape
    max_blocks = table.shape[0]
    kv_len = max_blocks * block_size
    pos = pos0 + jnp.arange(c)                          # (C,) absolute
    x = params["embed"][ids].astype(dtype)              # (1, C, D)
    # pool slot s of block j holds absolute position j*block_size + s
    # for this sequence (allocator hands blocks out in order); query p
    # attends slots with kvpos <= p. Padding rows (pos past the real
    # chunk) still compute but their writes hit scratch and their
    # logits are never read.
    kvpos = jnp.arange(kv_len)
    mask = kvpos[None, None, None, :] <= pos[None, None, :, None]
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
        q = rope_rows(q.transpose(1, 0, 2, 3), pos).transpose(1, 0, 2, 3)
        k = rope_rows(k.transpose(1, 0, 2, 3), pos).transpose(1, 0, 2, 3)
        k_pool = k_pool.at[li, blk_idx, blk_off].set(
            k[0].astype(k_pool.dtype))
        v_pool = v_pool.at[li, blk_idx, blk_off].set(
            v[0].astype(v_pool.dtype))
        kc = k_pool[li][table].reshape(1, kv_len, n_kv, hd)
        vc = v_pool[li][table].reshape(1, kv_len, n_kv, hd)
        kcx = expand_kv(kc, n_heads).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kcx) * hd ** -0.5               # (1,H,C,kv_len)
        s = jnp.where(mask, s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1)
        vcx = expand_kv(vc, n_heads).astype(jnp.float32)
        attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vcx).astype(dtype)
        x = x + proj(blk, "wo", attn.reshape(1, c, -1), dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + mlp_paged(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = proj(params, "head", x[0, last_idx][None, :],
                   dtype).astype(jnp.float32)
    return logits[0], k_pool, v_pool
