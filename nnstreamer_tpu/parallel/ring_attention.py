"""Ring attention — sequence-parallel attention over the ``sp`` mesh axis.

Long-context support the reference does not have (SURVEY.md §5.7: its
"sequence" story is tensor_aggregator windowing). TPU-native design:

- the sequence dim is sharded over ``sp``; each device holds one Q/K/V
  block of shape (B, S/n, H, D);
- K/V blocks rotate around the ring with `lax.ppermute` (nearest-neighbor
  ICI hops — the mesh builder puts sp innermost for exactly this);
- softmax is accumulated online (flash-attention style running max /
  normalizer), so the full (S × S) score matrix never materializes and
  per-device HBM stays O(S/n · D + S/n · S/n);
- compute of block i overlaps the transfer of block i+1 because XLA
  schedules the ppermute DMA concurrently with the matmuls.

Causal masking uses the *rotating block index* so each device only
applies the triangular mask on its own diagonal block.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attn(q, k, v, m_prev, l_prev, o_prev, mask=None):
    """One online-softmax accumulation step.

    q: (B, Sq, H, D), k/v: (B, Sk, H, D); m/l: (B, H, Sq) running max /
    normalizer; o: (B, Sq, H, D) unnormalized output accumulator.
    """
    scale = q.shape[-1] ** -0.5
    # scores: (B, H, Sq, Sk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)                  # (B, H, Sq)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])            # (B, H, Sq, Sk)
    # fully-masked rows have s == m_new == NEG_INF → exp(0) = 1; zero them
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    corr = jnp.exp(m_prev - m_new)               # (B, H, Sq)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    o_new = o_prev * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def ring_attention(q, k, v, *, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, block_impl: str = "auto",
                   batch_axis: str = None):
    """Sequence-parallel attention. q/k/v: (B, S, H, D) with S sharded
    over `axis`; returns (B, S, H, D) with the same sharding.

    `batch_axis` composes sequence parallelism with data parallelism:
    B additionally shards over that mesh axis (each dp group runs its
    own independent ring over `axis`) — the dp×sp layout of a composed
    dp×tp×sp mesh. None keeps B replicated within the shard_map.

    block_impl picks the per-rotation block math: "pallas" runs each
    incoming K/V block through the flash_block_update kernel (MXU
    dot_generals, VMEM-resident online softmax), "xla" is the jnp
    einsum path, "auto" = pallas on TPU when the local block divides
    128 (CPU tests keep xla — interpret-mode grids are slow)."""

    n = mesh.shape[axis]
    s_local = q.shape[1] // n
    use_pallas = block_impl == "pallas" or (
        block_impl == "auto" and jax.default_backend() == "tpu"
        and s_local % 128 == 0)
    if use_pallas:
        return _ring_attention_pallas(q, k, v, mesh=mesh, axis=axis,
                                      causal=causal, n=n,
                                      batch_axis=batch_axis)

    def local(q, k, v):
        # q/k/v here: the per-device shard (B, S/n, H, D)
        b, sq, h, d = q.shape
        my = lax.axis_index(axis)

        m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        o0 = jnp.zeros((b, sq, h, d), jnp.float32)

        def attend(i, m, l, o, k_blk, v_blk):
            # blocks rotate j→j+1 each step, so after i steps this device
            # holds the block that started on device (my - i) mod n
            src = (my - i) % n
            if causal:
                # query global index = my*sq + iq; key global = src*sk + ik
                iq = my * sq + jnp.arange(sq)[:, None]
                ik = src * k_blk.shape[1] + jnp.arange(k_blk.shape[1])[None, :]
                mask = (iq >= ik)[None, None, :, :]
            else:
                mask = None
            return _block_attn(q, k_blk, v_blk, m, l, o, mask)

        def body(i, carry):
            m, l, o, k_blk, v_blk = carry
            m, l, o = attend(i, m, l, o, k_blk, v_blk)
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            return m, l, o, k_blk, v_blk

        # n-1 rotating steps, then the final block without the (wasted)
        # n-th ICI rotation
        m, l, o, k_last, v_last = lax.fori_loop(0, n - 1, body,
                                                (m0, l0, o0, k, v))
        m, l, o = attend(n - 1, m, l, o, k_last, v_last)
        l = jnp.maximum(l, 1e-20)
        out = o / l.transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    spec = P(batch_axis, axis, None, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _ring_attention_pallas(q, k, v, *, mesh, axis, causal, n,
                           batch_axis=None):
    """Ring rotation with the Pallas flash block kernel doing each
    device's attend step (backends/pallas_ops.flash_block_update)."""
    from nnstreamer_tpu.backends.pallas_ops import (
        flash_block_update, flash_carry_finalize, flash_carry_init)

    def local(q, k, v):
        b, sq, h, d = q.shape
        my = lax.axis_index(axis)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        q_off = (my * sq).astype(jnp.int32)

        def flat(t):
            return t.transpose(0, 2, 1, 3).reshape(b * h, -1, d)

        m, l, acc = flash_carry_init(b * h, sq, d)

        def attend(i, m, l, acc, k_blk, v_blk):
            src = (my - i) % n
            k_off = (src * k_blk.shape[1]).astype(jnp.int32)
            return flash_block_update(
                qf, flat(k_blk), flat(v_blk), m, l, acc,
                q_offset=q_off, k_offset=k_off, causal=causal)

        def body(i, carry):
            m, l, acc, k_blk, v_blk = carry
            m, l, acc = attend(i, m, l, acc, k_blk, v_blk)
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            return m, l, acc, k_blk, v_blk

        m, l, acc, k_last, v_last = lax.fori_loop(
            0, n - 1, body, (m, l, acc, k, v))
        m, l, acc = attend(n - 1, m, l, acc, k_last, v_last)
        out = flash_carry_finalize(l, acc)
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3).astype(q.dtype)

    spec = P(batch_axis, axis, None, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def reference_attention(q, k, v, *, causal: bool = False):
    """Single-device ground truth for tests."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(q.dtype)
