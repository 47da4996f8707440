"""The dropless expert layer and its grouped products (pure jax around
`pallas_ops.grouped_matmul`).

Four families' programs run this module, so four cells of the benchmark
move with a line here: the sparse-expert family (`llm/sparse_moe.py`:
`keye_longctx_backlog`), the window family (`llm/window_moe.py`:
`trinity_mixed_backlog`), the latent family (`llm/latent_moe.py`:
`dsv2_code_backlog`) and the delta family (`llm/delta_moe.py`:
`kimi_reason_backlog`). The grouped products' row tile and their (tk,
tn) tile are chosen here and nowhere else (`expert_row_tile`,
`grouped`: from the static count of pair rows, `n_experts` and the
operands' type alone); `llm/families.py` asks the same rule for its
counters. `shared_mlp` is the MLP of a layer that keeps a shared expert
beside the routed ones (the window, the latent and the delta family's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm.parts import mlp_paged
from nnstreamer_tpu.llm.spec import LMSpec

# The pair rows up to which an expert of a layer gets a few rows at most
# (every decode bucket of the benchmark's cells, the chunks' small
# buckets): a visit of the grouped product there does nothing but stream
# the expert's matrices, and its row tile is chosen for that (PERF.md,
# PR 46). Beyond, a chunk's thousands of pair rows, the tile is PR 40's.
FEW_ROWS = 512

_F32 = jnp.float32


def route(blk, g, spec: LMSpec, dtype):
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


def fit(size: int, want: int) -> int:
    """The widest tile of at most `want` that divides `size` in whole
    lane tiles of 128; the whole of a `size` that has none."""
    t = min(want, size) // 128 * 128
    while t and size % t:
        t -= 128
    return t or size


def expert_row_tile(rows: int, n_experts: int) -> int:
    """The row tile of the expert layer's grouped products over `rows`
    (token, expert) pair rows dealt over `n_experts`: from the shapes
    alone. A visit is one row tile against one expert's matrices, and on
    the v5e it is bound by reading those matrices up to about 240
    bfloat16 rows (197 TFLOP/s over 819 GB/s) and by the matrix unit
    past that. Up to `FEW_ROWS` the tile is 64: a visit costs the same
    from 8 rows to 64 (the matrix unit loads an expert's blocks whatever
    the rows that pass them), and the wider tile has the fewer experts
    whose rows straddle an edge and are read twice. Beyond, the tile is
    a few times the mean rows an expert gets, so that an expert's rows
    span one or two tiles, within 128 to 256 (the sweeps on the chip:
    PERF.md, PR 40 and PR 46)."""
    if rows <= FEW_ROWS:
        return 64
    mean = max(1, rows // n_experts)
    return min(256, max(128, 1 << (2 * mean - 1).bit_length()))


def grouped(xs, w, counts, n_experts: int):
    """The grouped product ``xs[rows of expert e] @ w[e]`` for pair rows
    xs (R, K) sorted by expert, w (E, K, N), counts (E,), through
    `pallas_ops.grouped_matmul` at `expert_row_tile`'s row tile: every
    count of rows, every type (the compiler's `jax.lax.ragged_dot` takes
    all the rows of a decode bucket as one row tile and pays every
    touched expert a visit of it, 2.5 x the kernel's time at 512 pair
    rows and nowhere under it; and an expert's matrix in tiles wider
    than 2 MB, faster alone on the chip, slowed DeepSeek-V2's step:
    PERF.md, PR 46). Rows past the last expert's are the caller's to
    leave unread."""
    rows, (_, kk, nn) = xs.shape[0], w.shape
    # a (tk, tn) tile of an expert's matrix is 2 MB whatever the type; a
    # decode step is many short operations around these calls, so there
    # the compiler is told what they cost and schedules its copies by it
    tk = fit(kk, 2048 // xs.dtype.itemsize)
    return pallas_ops.grouped_matmul(
        xs, w, counts,
        tiling=(expert_row_tile(rows, n_experts), tk, fit(nn, 1024)),
        reckoned=rows <= FEW_ROWS)


def expert_layer(blk, g, live, spec: LMSpec, dtype):
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
    p, e = route(blk, g, spec, dtype)
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
    gu = grouped(xs, blk["ewi"].astype(dtype), counts, spec.n_experts)
    mid = jax.nn.silu(gu[:, :f]) * gu[:, f:]
    out = grouped(mid, blk["ewd"].astype(dtype), counts, spec.n_experts)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    # the rows past the last group are read by no pair that is `mine`:
    # this `where` is their only reader, and the kernel leaves them
    # unwritten
    out = out[inv].reshape(n, k, d).astype(_F32)
    y = jnp.sum(jnp.where(mine[..., None], out * p[..., None], 0.0),
                axis=1)
    away = jnp.sum(live[:, None] & ~mine, dtype=jnp.int32)
    return y.astype(dtype), counts, away


def shared_mlp(blk, u, live, dense: bool, spec: LMSpec, dtype, onto=None):
    """The MLP of a layer's normed input u (N, 1, D): where `dense`, the
    SwiGLU of `dense_width`; else a shared SwiGLU every token passes
    (`swi`, `swd`), unweighted, plus the routed experts' part. `onto`
    (the residual stream), where given, is what the MLP is added to, the
    shared expert's part first; a norm around the MLP stays at the call.
    Returns (the sum, the expert layer's counts with the pairs routed
    away last (experts_held + 1,) int32, or None for a dense layer)."""
    if dense:
        y = mlp_paged(blk, u, dtype)
        return (y if onto is None else onto + y), None
    y, counts, away = expert_layer(blk, u[:, 0], live, spec, dtype)
    # the shared expert through the dense products
    shared = mlp_paged({"wi": blk["swi"], "wd": blk["swd"]}, u, dtype)
    if onto is not None:
        shared = onto + shared
    return shared + y[:, None, :], jnp.concatenate([counts, away[None]])
