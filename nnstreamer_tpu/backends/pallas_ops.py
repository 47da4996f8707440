"""Built-in Pallas TPU kernels for streaming hot ops.

These cover the per-frame host-side ops the reference implements with Orc
SIMD on CPU (gsttensor_transform.c:463-493 typecast/arith kernels) — on
TPU they are VMEM-resident VPU kernels fused into one pass:

- ``normalize_u8``  — uint8 frame → (x - mean) / std float/bf16, the
  converter+transform ingest path in one kernel.
- ``clamp_scale``   — clamp + affine, the transform `clamp`/`stand` path.
- ``sparse_to_dense`` — device-side COO scatter (gsttensor_sparseutil.c
  to_dense analog, but on-chip).

Kernels run `interpret=True` automatically off-TPU so the same code path
is unit-testable on the CPU mesh (tests/conftest.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    """Whether a kernel traced now is interpreted: everywhere but on the
    TPU. Where a default device is set (`jax.default_device`: an engine
    kept on the host's CPU device beside the chip, as `chip_smoke.py`'s
    references are) it is that device's platform that decides, not the
    process's default backend."""
    device = jax.config.jax_default_device
    if device is None:
        return jax.default_backend() != "tpu"
    return getattr(device, "platform", device) != "tpu"


# -- normalize: uint8 → (x - mean) / std ------------------------------------

def _normalize_kernel(mean: float, inv_std: float, out_dtype, x_ref, o_ref):
    x = x_ref[:]
    if x.dtype in (jnp.uint8, jnp.int8, jnp.uint16, jnp.int16):
        # Mosaic can't lower narrow-int → float casts directly; widen first
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    o_ref[:] = ((x - mean) * inv_std).astype(out_dtype)


def normalize_u8(x, mean: float = 127.5, std: float = 127.5,
                 out_dtype=jnp.float32):
    """uint8 (..., W, C) → normalized float. One VMEM pass."""
    kern = functools.partial(_normalize_kernel, float(mean), 1.0 / float(std),
                             out_dtype)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=_interpret(),
    )(x)


# -- fused dynamic row quantization (W8A8 activations) -----------------------

def _quantize_rows_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)            # (bm, K) in VMEM
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)   # (bm, 1)
    q_ref[...] = jnp.clip(jnp.round(x / scale),
                          -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def quantize_rows_xla(x):
    """Plain-XLA twin of _quantize_rows_kernel — the one place the
    quantization formula lives outside the kernel, used for row counts
    the 8-row Mosaic sublane can't tile."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_rows(x, block_rows: int = 256):
    """(M, K) float → (int8 (M, K), f32 scales (M, 1)): symmetric
    per-row dynamic quantization in ONE VMEM pass.

    This is the W8A8 activation-quant hot path: expressed in XLA (amax
    reduce + round/clip/cast around the int8 dot) the quantization made
    ~3 HBM trips over the activations and cost MORE than the int8
    matmul it feeds (0.62 ms vs 0.13 ms at 16384×1024, the measured
    reason models/quant.py documented W8A8 at 0.74× bf16). Fused here:
    read x once, write int8 + one (M, 1) scale column. Row counts not
    divisible by the 8-row Mosaic sublane are zero-padded up to the
    next multiple of 8 and the outputs sliced back — pad rows quantize
    independently (per-row scales; amax 0 → scale 1 → q 0) so they
    never touch real rows, and the kernel keeps the single-HBM-trip
    property for ragged M (decode steps, tail microbatches) instead of
    falling back to the ~3-trip XLA path. `quantize_rows_xla` remains
    as the formula's plain-XLA twin for reference/testing."""
    m, k = x.shape
    m_pad = (-m) % 8
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
        m += m_pad
    bm = block_rows
    while bm > 8 and m % bm:
        bm //= 2
    q, s = pl.pallas_call(
        _quantize_rows_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        interpret=_interpret(),
    )(x)
    if m_pad:
        q, s = q[:m - m_pad], s[:m - m_pad]
    return q, s


# -- clamp + affine ----------------------------------------------------------

def _clamp_scale_kernel(lo: float, hi: float, scale: float, offset: float,
                        x_ref, o_ref):
    x = x_ref[:]
    x = jnp.clip(x, lo, hi)
    o_ref[:] = x * scale + offset


def clamp_scale(x, lo: float, hi: float, scale: float = 1.0,
                offset: float = 0.0):
    kern = functools.partial(_clamp_scale_kernel, float(lo), float(hi),
                             float(scale), float(offset))
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret(),
    )(x)


# -- sparse COO → dense on device -------------------------------------------

def sparse_to_dense(values, flat_indices, shape: Tuple[int, ...]):
    """Device-side scatter of a COO wire payload into a dense tensor.

    Scatter is a gather/scatter-unit op, not a Pallas sweet spot — XLA's
    native scatter lowering is already optimal, so this stays jnp (the
    kernel boundary is documented here deliberately).
    """
    n = 1
    for d in shape:
        n *= d
    dense = jnp.zeros((n,), values.dtype)
    dense = dense.at[flat_indices].set(values)
    return dense.reshape(shape)


# -- flash attention ---------------------------------------------------------

def _causal_mask(jnp_mod, row_off, col_off, bq, bk):
    """rows>=cols block mask from global offsets (shared by all three
    flash kernels so the mask semantics can never diverge)."""
    rows = row_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = col_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _online_softmax_update(q, k_blk, v_blk, m, l, acc, scale, mask):
    """One flash block update shared by both kernels: scaled QK^T on the
    MXU, optional mask, running max/normalizer, PV accumulation (f32)."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new[:, None])
    if mask is not None:
        p = jnp.where(s <= -1e29, 0.0, p)     # fully-masked rows stay 0
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[:, None] + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _flash_kernel(scale: float, causal: bool, bq: int, bk: int,
                  q_ref, k_ref, v_ref, o_ref):
    """One (batch·head, q-block) program: online-softmax over K/V blocks.

    K/V for this head live fully in VMEM (BlockSpec maps the whole
    sequence); the inner fori_loop streams them block-by-block through
    the MXU with flash-attention running max/normalizer accumulators, so
    the (S × S) score matrix never materializes.
    """
    q = q_ref[0]                              # (bq, D), input dtype

    s_total = k_ref.shape[1]
    qi = pl.program_id(1)
    n_kb = s_total // bk

    def body(masked, j, carry):
        # inputs stay in their (bf16) dtype into the MXU; accumulation
        # is f32 via preferred_element_type — the standard flash recipe
        k_blk = k_ref[0, pl.ds(j * bk, bk), :]
        v_blk = v_ref[0, pl.ds(j * bk, bk), :]
        mask = _causal_mask(jnp, qi * bq, j * bk, bq, bk) \
            if masked else None
        return _online_softmax_update(q, k_blk, v_blk, *carry, scale, mask)

    d = q.shape[-1]
    m0 = jnp.full((bq,), -1e30, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    carry = (m0, l0, a0)
    if causal:
        # K blocks entirely above the diagonal are fully masked — skip
        # them (halves the causal FLOPs). Blocks entirely BELOW the
        # diagonal need no mask either: with enough blocks per program
        # (long S), running them through an unmasked first loop saves
        # the per-block iota/compare/where VPU lane work and measured
        # +13% at S=8192 (interleaved A/B, round 5). With few blocks
        # (S=2048 → 4) the second loop's pipeline restart costs more
        # than the mask it saves, so short grids keep one masked loop.
        upper = pl.cdiv((qi + 1) * bq, bk)            # first masked blk
        if n_kb >= 8:
            full = (qi * bq) // bk                    # blks fully below
            carry = jax.lax.fori_loop(
                0, full, functools.partial(body, False), carry)
            carry = jax.lax.fori_loop(
                full, upper, functools.partial(body, True), carry)
        else:
            carry = jax.lax.fori_loop(
                0, upper, functools.partial(body, True), carry)
    else:
        carry = jax.lax.fori_loop(
            0, n_kb, functools.partial(body, False), carry)
    m, l, acc = carry
    l = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def _flash_kgrid_kernel(scale: float, causal: bool, bq: int, bk: int,
                        q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr):
    """K-blocked grid program for LONG sequences: grid is
    (batch·head, q_blocks, k_blocks) with k innermost, so K/V stream
    through VMEM one (bk, D) block at a time — per-step VMEM is O(bq·D +
    bk·D) regardless of S. The online-softmax carry (m, l, acc) lives in
    VMEM scratch, which persists across sequential grid steps on TPU."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # K blocks fully above the diagonal contribute nothing
        run = (ki * bk) <= (qi * bq + bq - 1)
    q = q_ref[0]
    k_blk = k_ref[0]
    v_blk = v_ref[0]

    @pl.when(run)
    def _step():
        m = m_scr[0, :]
        l = l_scr[0, :]
        acc = acc_scr[...]
        mask = _causal_mask(jnp, qi * bq, ki * bk, bq, bk) \
            if causal else None
        m, l, acc = _online_softmax_update(q, k_blk, v_blk, m, l, acc,
                                           scale, mask)
        m_scr[...] = jnp.broadcast_to(m[None, :], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l[None, :], l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[0, :], 1e-20)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _flash_attention_kgrid(qf, kf, vf, *, scale: float, causal: bool,
                           bq: int, bk: int, interpret: bool):
    bh, s, d = qf.shape
    kern = functools.partial(_flash_kgrid_kernel, scale, causal, bq, bk)
    return pl.pallas_call(
        kern,
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, k: (i, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, k: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), qf.dtype),
        scratch_shapes=[
            pltpu.VMEM((8, bq), jnp.float32),       # m (sublane-repl)
            pltpu.VMEM((8, bq), jnp.float32),       # l
            pltpu.VMEM((bq, d), jnp.float32),       # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)


#: VMEM budget for holding a head's full K+V in the single-program
#: kernel; beyond it the K-grid streaming path takes over (long context)
_FLASH_VMEM_KV_BYTES = 8 << 20


def _auto_block(s: int, want: int) -> int:
    """Largest power-of-two block ≤ `want` that divides `s` (≥8 for
    Mosaic sublane tiling)."""
    b = min(want, s)
    while b > 8 and s % b:
        b //= 2
    return b


def _flash_plan(s: int, d: int, itemsize: int,
                block_q: int = 0, block_k: int = 0):
    """(kgrid?, bq, bk) for flash_attention — the per-path defaults the
    round-5 quiet-chip sweep landed on (see flash_attention docstring);
    pure so the choice is pinned by unit test."""
    kgrid = 2 * s * d * itemsize > _FLASH_VMEM_KV_BYTES
    want_q, want_k = (1024, 1024) if kgrid else (512, 512)
    bq = min(block_q or _auto_block(s, want_q), s)
    bk = min(block_k or _auto_block(s, want_k), s)
    return kgrid, bq, bk


def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: int = 0, block_k: int = 0):
    """Fused attention for (B, S, H, D) tensors — the transformer hot op
    as a Pallas kernel (flash-attention online softmax; S×S scores never
    leave VMEM). Block sizes auto-tune per path to the largest dividing
    powers of two ≤ (512, 512) VMEM-resident / (1024, 1024) K-grid —
    round-5 sweep on the quiet chip: at S=2048 bk=512 beats the old
    bk=1024 default 0.61 vs 0.73 ms causal (28.8% vs 23.8% MFU) and
    0.64 vs 0.79 ms non-causal (54.9% vs 44.4%), the smaller K block
    wasting fewer masked FLOPs on diagonal blocks; the streaming K-grid
    runs fewer, larger steps best (S=32768: 30.4 ms/36.8% MFU at
    1024² vs 34.8/32.1 at the old default; 1024×2048 exceeds the 16M
    VMEM scoped limit). S=8192 is insensitive (±1.4%). Requires
    S % block == 0 (pad upstream); falls back to interpret mode off-TPU
    like every kernel here.

    Long sequences: when a head's full K+V would exceed the VMEM budget
    (S ≳ 16k at D=128), the kernel switches to a K-blocked grid that
    streams K/V through VMEM with scratch-carried online-softmax state —
    per-step VMEM is independent of S, so S=64k+ compiles and runs."""
    b, s, h, d = q.shape
    kgrid, bq, bk = _flash_plan(s, d, q.dtype.itemsize, block_q, block_k)
    if s % bq or s % bk:
        raise ValueError(
            f"flash_attention needs seq len {s} divisible by block sizes "
            f"({bq}, {bk}); pad the sequence upstream")
    scale = d ** -0.5

    def bhsd(t):
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    qf, kf, vf = bhsd(q), bhsd(k), bhsd(v)
    if kgrid:
        out = _flash_attention_kgrid(qf, kf, vf, scale=scale,
                                     causal=causal, bq=bq, bk=bk,
                                     interpret=_interpret())
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    kern = functools.partial(_flash_kernel, scale, causal, bq, bk)
    out = pl.pallas_call(
        kern,
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=_interpret(),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_block_kernel(scale: float, bk: int, causal: bool,
                        qoff_ref, koff_ref,
                        q_ref, k_ref, v_ref, m_ref, l_ref, a_ref,
                        mo_ref, lo_ref, ao_ref):
    """Ring-attention block update: continue online softmax over ONE
    incoming K/V block, carrying (m, l, acc) in/out. Global query/key
    offsets arrive in SMEM so the causal mask works on rotated blocks;
    causal is trace-time static (no mask work on the non-causal path).
    m/l carry a (8, bq) sublane-replicated layout — Mosaic requires
    (8, 128)-tileable blocks, so the per-row scalar rides all 8 sublanes."""
    q = q_ref[0]                                  # (bq, D)
    m = m_ref[0, 0]                               # (bq,) from sublane 0
    l = l_ref[0, 0]
    acc = a_ref[0]                                # (bq, D)
    qi = pl.program_id(1)
    bq = q.shape[0]
    s_k = k_ref.shape[1]
    qoff = qoff_ref[0] + qi * bq
    koff = koff_ref[0]

    def body(j, carry):
        k_blk = k_ref[0, pl.ds(j * bk, bk), :]
        v_blk = v_ref[0, pl.ds(j * bk, bk), :]
        mask = _causal_mask(jnp, qoff, koff + j * bk, bq, bk) \
            if causal else None
        return _online_softmax_update(q, k_blk, v_blk, *carry, scale, mask)

    n_kb = s_k // bk
    if causal:
        # sub-blocks whose first key index exceeds this program's last
        # query index are fully masked: bound the loop instead of zeroing
        # their scores after full MXU work (_flash_kernel's same skip)
        row_max = qoff + bq - 1
        upper = jnp.clip(
            jax.lax.div(row_max - koff, jnp.int32(bk)) + 1, 0, n_kb)
    else:
        upper = n_kb
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
    mo_ref[0] = jnp.broadcast_to(m[None, :], (8, m.shape[0]))
    lo_ref[0] = jnp.broadcast_to(l[None, :], (8, l.shape[0]))
    ao_ref[0] = acc


def flash_block_update(q, k_blk, v_blk, m, l, acc, *, q_offset, k_offset,
                       causal: bool, block_q: int = 128,
                       block_k: int = 128):
    """One ring-attention step as a Pallas kernel: q (BH, Sq, D) attends
    an incoming K/V block (BH, Sk, D), updating the flash carry
    m/l (BH, Sq) f32 and acc (BH, Sq, D) f32. Offsets are the global
    sequence positions of this device's queries / the rotated block's
    keys (traced scalars — they change every ring step)."""
    bh, sq, d = q.shape
    sk = k_blk.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(
            f"flash_block_update needs Sq={sq}, Sk={sk} divisible by "
            f"({bq}, {bk})")
    scale = d ** -0.5
    kern = functools.partial(_flash_block_kernel, scale, bk, bool(causal))
    grid = (bh, sq // bq)
    scalars = [jnp.asarray([v], jnp.int32) for v in (q_offset, k_offset)]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk_q = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0))
    blk_kv = pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0))
    blk_m = pl.BlockSpec((1, 8, bq), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[smem, smem,
                  blk_q, blk_kv, blk_kv, blk_m, blk_m, blk_q],
        out_specs=[blk_m, blk_m, blk_q],
        out_shape=[jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
                   jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
                   jax.ShapeDtypeStruct((bh, sq, d), jnp.float32)],
        # donate the carry: each ring step updates (m, l, acc) in place
        # instead of allocating three fresh HBM buffers per rotation
        input_output_aliases={5: 0, 6: 1, 7: 2},
        interpret=_interpret(),
    )(*scalars, q, k_blk, v_blk, m, l, acc)


def _selected_block_kernel(scale: float, tile_ref,
                           q_ref, k_ref, v_ref, key_ref, t_ref, cut_ref,
                           m_ref, l_ref, a_ref, mo_ref, lo_ref, ao_ref):
    """Selected-attention tile update: `flash_block_update`'s carry
    under the chunk selection's mask, for one KV head and one block of
    queries with all the query heads of the group. The mask is one for
    the group (``key > T or (key == T and position <= P)``), built once
    and handed to each head's `_online_softmax_update`; scores and
    probabilities never leave VMEM. The carry's m / l block is
    (group, bq): a head a sublane, no replication needed."""
    grp, bq = q_ref.shape[1], q_ref.shape[2]
    sk = k_ref.shape[0]

    def ordered(u):
        # uint32 order as int32 order (Mosaic compares signed vectors)
        return jax.lax.bitcast_convert_type(u, jnp.int32) ^ jnp.int32(-2**31)

    key, t = ordered(key_ref[...]), ordered(t_ref[...])   # (bq, sk), (bq, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, sk), 1)
    cut = cut_ref[...] - tile_ref[0] * sk                 # tile-relative
    mask = (key > t) | ((key == t) & (col <= cut))
    for r in range(grp):
        mo_ref[0, r], lo_ref[0, r], ao_ref[0, r] = _online_softmax_update(
            q_ref[0, r], k_ref[...], v_ref[...],
            m_ref[0, r], l_ref[0, r], a_ref[0, r], scale, mask)


def selected_block_update(q, k_blk, v_blk, keys, t, cut, tile, m, l, acc,
                          *, block_q: int = 128, interpret=None):
    """One context tile of the sparse-expert chunk's attention walk
    (llm/sparse_moe.py) as a Pallas kernel. q (Hkv, G, C, D): C queries,
    G query heads a KV head; k_blk, v_blk (Sk, Hkv, D): the tile's keys
    and values; keys (C, S) uint32: every query's selection key of every
    context slot, of which this is tile number `tile` (a traced scalar)
    of Sk slots; t (C,) uint32 and cut (C,) int32: query c attends slot
    s where ``keys[c, s] > t[c]``, or ``keys[c, s] == t[c]`` and
    ``s <= cut[c]``. Updates the flash carry m, l (Hkv, G, C) f32 and
    acc (Hkv, G, C, Dv) f32 in place; a query the tile selects nothing
    for keeps its carry. Needs D a multiple of 128 (a KV head is a lane
    tile of the (Sk, Hkv * D) view) unless Hkv is 1. The values may be
    of another width than the keys, v_blk (Sk, Hkv, Dv) under the same
    rule (the latent family's expanded heads: llm/latent_moe.py).

    A program takes `block_q` queries against the whole tile: on the
    v5e, at Sk 1024 and 8 heads of 128 a group, 128 queries read 0.40 ms
    a tile of 2,048 queries, 256 0.50, 512 0.47; the tile in two key
    blocks of 512 read 0.55-0.78 (PERF.md, PR 32)."""
    nkv, grp, c, d = q.shape
    sk = k_blk.shape[0]
    bq = min(block_q, c)
    if c % bq or keys.shape[1] % sk:
        raise ValueError(
            f"selected_block_update needs C={c} divisible by {bq} and "
            f"S={keys.shape[1]} by Sk={sk}")
    dv = v_blk.shape[2]
    kern = functools.partial(_selected_block_kernel, d ** -0.5)
    blk_q = pl.BlockSpec((1, grp, bq, d), lambda g, i, j: (g, 0, i, 0))
    blk_a = pl.BlockSpec((1, grp, bq, dv), lambda g, i, j: (g, 0, i, 0))
    blk_k = pl.BlockSpec((sk, d), lambda g, i, j: (0, g))
    blk_v = pl.BlockSpec((sk, dv), lambda g, i, j: (0, g))
    blk_c = pl.BlockSpec((bq, 1), lambda g, i, j: (i, 0))
    blk_m = pl.BlockSpec((1, grp, bq), lambda g, i, j: (g, 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nkv, c // bq),
        in_specs=[blk_q, blk_k, blk_v,
                  pl.BlockSpec((bq, sk), lambda g, i, j: (i, j[0])),
                  blk_c, blk_c, blk_m, blk_m, blk_a],
        out_specs=[blk_m, blk_m, blk_a])
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l.shape, jnp.float32),
                   jax.ShapeDtypeStruct(acc.shape, jnp.float32)],
        # the carry is updated in place, as flash_block_update's
        input_output_aliases={7: 0, 8: 1, 9: 2},
        name="selected_block_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(tile, jnp.int32).reshape(1), q,
      k_blk.reshape(sk, nkv * d), v_blk.reshape(sk, nkv * dv), keys,
      t[:, None], cut[:, None], m, l, acc)


def block_reach(q0, bq: int, s0, sk: int, window: int = 0):
    """What the `bq` queries at positions from `q0` on see of the `sk`
    context slots from `s0` on, query q seeing slot s where ``s <= q``
    and, under a `window` > 0, ``s > q - window``. Returns (skip: no
    query sees any slot; clear: every query sees every slot); neither is
    the edge, where a mask decides. In arithmetic that the host's ints
    (and numpy's arrays) and a kernel's traced scalars both take."""
    q1, s1 = q0 + (bq - 1), s0 + (sk - 1)         # last query, last slot
    skip, clear = s0 > q1, s1 <= q0
    if window:
        skip = skip | (s1 <= q0 - window)
        clear = clear & (s0 > q1 - window)
    return skip, clear


#: rows of queries (heads of a group x queries) a program of the causal
#: tile update takes: what `selected_block_update`'s 128 queries of 8 heads
#: were read best at (PR 32), and the readings in `causal_block_update`
_BLOCK_ROWS = 1024


def causal_block_q(c: int, grp: int) -> int:
    """Queries a program of `causal_block_update` takes of a chunk of
    `c`, `grp` query heads a KV head: `_BLOCK_ROWS` rows a program or the
    most under it, a power of two that divides `c`, never under 128 (a
    shorter chunk whole)."""
    bq = 128
    while bq * 2 * grp <= _BLOCK_ROWS and c % (bq * 2) == 0:
        bq *= 2
    return min(bq, c)


def _causal_block_kernel(scale: float, window: int, pos_ref,
                         q_ref, k_ref, v_ref, m_ref, l_ref, a_ref,
                         mo_ref, lo_ref, ao_ref):
    """Causal tile update: `_selected_block_kernel`'s carry and layout
    under a mask that positions alone decide, so no array says it. A
    program's block of queries starts at position ``pos_ref[0] + i * bq``
    and the tile at slot ``pos_ref[1]``; `block_reach` says from the two
    which of three bodies runs: the update with no mask, the update under
    the mask made here from two iotas, or the carry handed through."""
    grp, bq = q_ref.shape[1], q_ref.shape[2]
    sk = k_ref.shape[0]
    q0 = pos_ref[0] + pl.program_id(1) * bq
    s0 = pos_ref[1]
    skip, clear = block_reach(q0, bq, s0, sk, window)

    def update(mask):
        for r in range(grp):
            mo_ref[0, r], lo_ref[0, r], ao_ref[0, r] = _online_softmax_update(
                q_ref[0, r], k_ref[...], v_ref[...],
                m_ref[0, r], l_ref[0, r], a_ref[0, r], scale, mask)

    @pl.when(clear)
    def _clear():
        update(None)

    @pl.when(jnp.logical_not(clear | skip))
    def _edge():
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, sk), 0)
        col = s0 + jax.lax.broadcasted_iota(jnp.int32, (bq, sk), 1)
        mask = col <= row
        if window:
            mask = mask & (col > row - window)
        update(mask)

    @pl.when(skip)
    def _skip():
        mo_ref[...] = m_ref[...]
        lo_ref[...] = l_ref[...]
        ao_ref[...] = a_ref[...]


def causal_block_update(q, k_blk, v_blk, qpos0, slot0, m, l, acc, *,
                        window: int = 0, block_q: int = 0, interpret=None):
    """One context tile of a chunk's attention walk under the causal
    edge and, where `window` > 0, a window (llm/window_moe.py,
    llm/latent_moe.py) as a Pallas kernel: `selected_block_update` for a
    mask that is a function of positions. q (Hkv, G, C, D): C queries at
    the consecutive positions from `qpos0` on (a traced scalar); k_blk
    (Sk, Hkv, D), v_blk (Sk, Hkv, Dv): the keys and values of the context
    slots from `slot0` on (a traced scalar); query c attends slot s where
    ``slot0 + s <= qpos0 + c`` and, under a window, ``slot0 + s > qpos0 +
    c - window``. Updates the flash carry m, l (Hkv, G, C) f32 and acc
    (Hkv, G, C, Dv) f32 in place; a query that sees no slot of the tile
    keeps its carry to the bit. The same widths as the selected form: D
    and Dv multiples of 128 unless Hkv is 1.

    A program takes `block_q` queries (0: `causal_block_q`'s) with all
    the heads of their group against the whole tile, in the one of three
    bodies `block_reach` names from its first position and `slot0`: a
    block that sees every slot runs the update with no mask (no iota,
    compare or select), a block that sees none copies its carry through
    and runs neither product, the blocks between make the mask from two
    iotas. A row's result does not depend on the block it rides in.

    On the v5e, a walk of 6 tiles of 1,024 slots under 2,048 queries at
    position 4,096, ms a tile (PERF.md, PR 42; the selected form at 128
    queries beside it): 128 heads of one, D 256 and Dv 128 (the latent
    family's expanded heads), 3.12 selected, 2.92 at 128 queries, 2.32 at
    256, 2.10 at 512, 2.01 at 1,024 (a chunk of 1,024 at position 0: 1.72,
    1.71, -, 1.34, 1.28); 8 KV heads of 6 of 128 (the window family's),
    0.565 selected, 0.515 at 128, 0.628 at 256, and under a window of
    4,096 at position 8,192 0.562, 0.481, 0.568; 4 KV heads of 8, 0.369,
    0.343, 0.410. The mask's array was a fifteenth of the time, the
    programs' number most of it: the rule is rows, not bytes."""
    nkv, grp, c, d = q.shape
    sk, dv = k_blk.shape[0], v_blk.shape[2]
    bq = min(block_q or causal_block_q(c, grp), c)
    if c % bq:
        raise ValueError(
            f"causal_block_update needs C={c} divisible by {bq}")
    kern = functools.partial(_causal_block_kernel, d ** -0.5, int(window))
    blk_q = pl.BlockSpec((1, grp, bq, d), lambda g, i, p: (g, 0, i, 0))
    blk_a = pl.BlockSpec((1, grp, bq, dv), lambda g, i, p: (g, 0, i, 0))
    blk_k = pl.BlockSpec((sk, d), lambda g, i, p: (0, g))
    blk_v = pl.BlockSpec((sk, dv), lambda g, i, p: (0, g))
    blk_m = pl.BlockSpec((1, grp, bq), lambda g, i, p: (g, 0, i))
    # what a program keeps in fast memory: its blocks buffered twice, the
    # carry's in and out, and a head's scores, probabilities and mask;
    # past the compiler's own limit of 16 MB a kernel the call asks for
    # what it needs, as `grouped_matmul` does
    item = q.dtype.itemsize
    need = (2 * (item * (grp * bq * d + sk * (d + dv))
                 + 8 * grp * bq * (dv + 2))
            + bq * sk * (9 + item))
    limit = need + (4 << 20) if need > (14 << 20) else None
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nkv, c // bq),
            in_specs=[blk_q, blk_k, blk_v, blk_m, blk_m, blk_a],
            out_specs=[blk_m, blk_m, blk_a]),
        out_shape=[jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l.shape, jnp.float32),
                   jax.ShapeDtypeStruct(acc.shape, jnp.float32)],
        # the carry is updated in place, as flash_block_update's
        input_output_aliases={4: 0, 5: 1, 6: 2},
        name="causal_block_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=limit),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray([qpos0, slot0], jnp.int32), q,
      k_blk.reshape(sk, nkv * d), v_blk.reshape(sk, nkv * dv), m, l, acc)


# -- grouped matmul -----------------------------------------------------------

def group_visits(group_sizes, m: int, tm: int):
    """The grouped product's plan for rows sorted by group, in row tiles
    of `tm`: one visit for every (row tile, group) pair that shares
    rows, in row order. Returns (offsets (G + 1,): group g's rows are
    ``offsets[g]`` to ``offsets[g + 1]``; group (V,) and row tile (V,) of
    each visit, V = m / tm + G - 1 the most there can be; the visits
    there are, () int32). A group of no rows is not visited."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    first = (ends - group_sizes) // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    done = jnp.cumsum(tiles)                  # visits up to each group's last
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= done[None, :], axis=1,
                                dtype=jnp.int32), g - 1)
    tile = first[group] + v - (done[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, jnp.clip(tile, 0, m // tm - 1), done[-1]


def _grouped_matmul_kernel(tm: int, tk: int, tn: int, off_ref, group_ref,
                           tile_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    """One (visit, N tile, K tile) step: the visit's row tile, whole in
    K, against a (tk, tn) tile of its group's matrix, summed over K
    tiles in float32; after the last, the rows that are the group's go
    to their N tile of the visit's output rows, which stay in fast
    memory while consecutive visits share the row tile."""
    v, n, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a K or an N that is no whole number of lane tiles is one tile (the
    # caller's `tiling`) and is taken whole: Mosaic has no index to prove
    # aligned
    lhs = lhs_ref[...] if tk % 128 else lhs_ref[
        :, pl.ds(pl.multiple_of(k * tk, tk), tk)]
    acc_ref[...] += jnp.dot(lhs, rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        at = slice(None) if tn % 128 else pl.ds(
            pl.multiple_of(n * tn, tn), tn)
        out_ref[:, at] = jnp.where(
            mine, acc_ref[...],
            out_ref[:, at].astype(jnp.float32)).astype(out_ref.dtype)


def grouped_matmul(lhs, rhs, group_sizes, *, tiling, reckoned=False,
                   interpret=None):
    """``lhs[rows of group g] @ rhs[g]`` for every group: lhs (M, K)
    with its rows sorted by group, rhs (G, K, N), group_sizes (G,) int32
    summing to at most M. What `jax.lax.ragged_dot` computes, on the grid
    of the public megablox grouped product with the row tile the caller
    chooses: `tiling` = (tm, tk, tn), tk dividing K and tn dividing N.
    One visit for every (row tile, group) pair that shares rows
    (`group_visits`; a run-time count: a group of no rows costs
    nothing, and neither do the rows past the last group); products of
    the operands' type summed in float32 over K tiles; the result in
    lhs's type. **Rows past the last group belong to no group and are
    never written: what they hold is not defined** (the interpreter
    leaves NaN there), so the caller reads them behind a mask. M is
    filled up to a whole number of row tiles here.

    The visits are the outermost grid dimension: a visit's rows (tm, K)
    and its output rows (tm, N) stay in fast memory while the group's
    matrix streams through once, so the rows are read once a visit, not
    once an N tile as in megablox's order.

    `reckoned`: hand the compiler the call's cost (every row against a
    matrix, and the bytes of as many matrices as there are groups, or
    rows if those are fewer: the most a call can visit). A kernel's
    time is otherwise nothing to the compiler, which then starts few of
    its own copies ahead of the operations around the call (a decode
    step of DeepSeek-V2 compiled for a described v5e: 137 with its own
    grouped product, 54 around this kernel, 101 around it reckoned:
    PERF.md, PR 46)."""
    tm, tk, tn = tiling
    m, kk = lhs.shape
    groups, _, nn = rhs.shape
    if kk % tk or nn % tn or (tk % 128 and tk != kk) or (tn % 128
                                                            and tn != nn):
        raise ValueError(f"grouped_matmul needs K={kk} divisible by tk={tk} "
                         f"and N={nn} by tn={tn}, in whole lane tiles of "
                         f"128 or as one tile")
    if m % tm:
        lhs = jnp.pad(lhs, ((0, -m % tm), (0, 0)))
    rows = lhs.shape[0]
    offsets, group, tile, visits = group_visits(group_sizes, rows, tm)
    # what a visit keeps in fast memory, its blocks buffered twice; past
    # the compiler's own limit of 16 MB a kernel (float32 at a hidden size
    # of 5,120: 22 MB) the call asks for what it needs
    need = (2 * lhs.dtype.itemsize * (tm * kk + tm * nn + tk * tn)
            + 4 * tm * tn)
    limit = need + (4 << 20) if need > (14 << 20) else None
    cost = pl.CostEstimate(
        flops=2 * rows * kk * nn, transcendentals=0,
        bytes_accessed=lhs.dtype.itemsize * (
            min(groups, rows) * kk * nn + rows * (kk + nn))
    ) if reckoned else None
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm, tk, tn),
        cost_estimate=cost,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, nn // tn, kk // tk),
            in_specs=[
                pl.BlockSpec((tm, kk), lambda v, n, k, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, tk, tn),
                             lambda v, n, k, o, g, t: (g[v], k, n))],
            out_specs=pl.BlockSpec((tm, nn),
                                   lambda v, n, k, o, g, t: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, nn), lhs.dtype),
        name="grouped_matmul",
        # a row tile's output is revisited by the next visit: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=_interpret() if interpret is None else interpret,
    )(offsets, group, tile, lhs, rhs)
    return out[:m]


def flash_carry_init(bh: int, sq: int, d: int):
    """Fresh (m, l, acc) carry for flash_block_update — m/l in the
    (BH, 8, Sq) sublane-replicated layout the kernel requires."""
    return (jnp.full((bh, 8, sq), -1e30, jnp.float32),
            jnp.zeros((bh, 8, sq), jnp.float32),
            jnp.zeros((bh, sq, d), jnp.float32))


def flash_carry_finalize(l, acc):
    """acc / l → attention output (BH, Sq, D)."""
    return acc / jnp.maximum(l[:, 0, :], 1e-20)[..., None]
