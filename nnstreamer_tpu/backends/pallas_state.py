"""The delta family's decode update of a state pool by slot
(llm/delta_moe.py), one Pallas kernel a layer: a row's state is read
through its slot, advanced in fast memory and written back where it lay.

A module of its own: a kernel's serialized module carries its call sites'
paths and lines, so a kernel added above another in a shared file would
cost every program that holds the other one compile (PERF.md, PR 44).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.backends.pallas_ops import _interpret

# Heads of a row's state one program takes: a block of `STATE_HEADS` x d x
# dv float32 each way, two buffers deep (8 heads of 128 x 128: 512 KB a
# block, 2 MB of fast memory)
STATE_HEADS = 8


def _delta_decode_kernel(hb: int, dv: int, meta_ref, slots_ref, col_ref,
                         row_ref, s_ref, o_ref, out_ref):
    """One (row, group of `hb` heads) program: each head's (d, dv) state
    decayed, read for what it holds of k and of q, and updated, on the
    vector unit in float32. `col_ref` (1, 1, d, 3 hb): q, k and the
    decays, a value a sublane, a head a lane; `row_ref` (1, 1, hb, 3 dv):
    v, beta and q . k, a head a sublane."""
    r = pl.program_id(0)
    live = r < meta_ref[1]

    @pl.when(live)
    def _advance():
        for j in range(hb):
            q, k, a = (col_ref[0, 0, :, i * hb + j:i * hb + j + 1]
                       for i in range(3))                       # (d, 1)
            v, beta, qk = (row_ref[0, 0, j:j + 1, i * dv:(i + 1) * dv]
                           for i in range(3))                   # (1, dv)
            decayed = a * s_ref[0, 0, j]
            held = jnp.sum(k * decayed, axis=0, keepdims=True)
            seen = jnp.sum(q * decayed, axis=0, keepdims=True)
            w = beta * (v - held)
            o_ref[0, 0, j:j + 1, :] = seen + qk * w
            out_ref[0, 0, j] = decayed + k * w

    @pl.when(jnp.logical_not(live))
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_decode_update(q, k, v, a, beta, s_pool, li, slots, n_live, *,
                        heads: int | None = None, interpret=None):
    """`delta_moe.delta_step` for layer `li` of the state pool, the rows'
    states moved through their slots: q, k, a (B, H, d) float32 (a the
    decays, ``exp(g)``), v (B, H, dv), beta (B, H); s_pool (layers, slots,
    H, d, dv) float32, whole (`li` is traced: an ``s_pool[li]`` in front
    of the call would slice a layer out); slots (B,) int32; n_live ()
    int32, the real rows (the first ones). Returns (o (B, H, dv) float32,
    zeros for the rows past `n_live`; the pool, layer `li`'s slots of the
    live rows advanced by one token and nothing else written). `heads`:
    the heads of a row one program takes, `STATE_HEADS` where not given.

    `li`, `n_live` and `slots` ride scalar prefetch; the pool's block is
    ``(1, 1, heads, d, dv)`` at ``(li, slots[r], h)`` for the input and
    for the output, and the pool is aliased input to output: the pipeline
    brings a row's heads in, the body advances them and the pipeline
    writes them back where they lay. No gathered copy exists and nothing
    is scattered. **The aliased update is sound because the live rows'
    slots are distinct** (`paged_cache.py`'s allocator grants a slot to
    one sequence at a time): no program reads a block another writes. The
    rows past `n_live` all name the scratch slot 0, and one such program
    after another would race the pipeline's prefetch against its
    write-back there: they do nothing, and their blocks are the last live
    row's last, which the pipeline neither fetches nor writes again, so a
    padding row moves no state at all.

    The vectors indexed by the state's row axis (q, k, a) are handed in
    transposed and side by side, ``(B, H / heads, d, 3 heads)``: a value a
    sublane, as the product with a (d, dv) state wants them. The sums over
    d run down the sublanes, in another order than `delta_step`'s."""
    b, h, d = q.shape
    dv = v.shape[-1]
    hb = min(heads or STATE_HEADS, h)
    if h % hb or s_pool.shape[2:] != (h, d, dv) or s_pool.dtype != jnp.float32:
        raise ValueError(
            f"delta_decode_update: a pool of {s_pool.shape} {s_pool.dtype} "
            f"for {h} heads of {d} x {dv} float32 in groups of {hb}")
    nh = h // hb
    f32 = jnp.float32
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    # (B, H, d) -> (B, H / hb, d, hb): a group's heads as lanes
    cols = jnp.concatenate(
        [x.reshape(b, nh, hb, d).swapaxes(2, 3) for x in (q, k, a)], axis=-1)
    qk = jnp.sum(q * k, axis=-1, keepdims=True)
    rows = jnp.concatenate(
        [v, jnp.broadcast_to(beta[..., None], v.shape),
         jnp.broadcast_to(qk, v.shape)], axis=-1).reshape(b, nh, hb, 3 * dv)

    def group(r, g, meta):
        """Row r's group g, or for a row past the live ones the last
        group of the last live row (the block the pipeline holds)."""
        last = jnp.maximum(meta[1] - 1, 0)
        return jnp.minimum(r, last), jnp.where(r < meta[1], g, nh - 1)

    def state_at(r, g, meta, slots):
        r, g = group(r, g, meta)
        return meta[0], slots[r], g, 0, 0

    def vectors_at(r, g, meta, slots):
        return (*group(r, g, meta), 0, 0)

    state = pl.BlockSpec((1, 1, hb, d, dv), state_at)
    o, s_pool = pl.pallas_call(
        functools.partial(_delta_decode_kernel, hb, dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nh),
            in_specs=[pl.BlockSpec((1, 1, d, 3 * hb), vectors_at),
                      pl.BlockSpec((1, 1, hb, 3 * dv), vectors_at),
                      state],
            out_specs=[pl.BlockSpec((1, 1, hb, dv),
                                    lambda r, g, *_: (r, g, 0, 0)),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((b, nh, hb, dv), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # the pool, after the two prefetched scalars and the vectors
        input_output_aliases={4: 1},
        # what the rule needs: every state once each way
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * d * dv, transcendentals=0,
            bytes_accessed=2 * 4 * b * h * d * dv),
        name="delta_decode_update",
        # the padding rows revisit the last live row's block: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.stack([li, n_live]).astype(jnp.int32), slots.astype(jnp.int32),
      cols, rows, s_pool)
    return o.reshape(b, h, dv), s_pool
