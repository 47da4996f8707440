"""The decoder whose layers are gated delta-rule linear attention (KDA) or
latent attention, and keep two kinds of cache in one model: by *slot*, a
float32 state and the tails of three short convolutions a KDA layer; by
*block*, the latent layers' pool of one compressed row a token (pure jax,
jitted by llm_exec as ``jit_delta_moe_decode_step`` and
``jit_delta_moe_prefill_chunk``).

What is this family's own lives here: the KDA layer (its convolutions with
their tails, its decay and gates, the delta rule's decode update by slot
and its chunk in the closed form, the state handed run to run) and the two
programs that walk the kinds of layer. A LATENT layer is the latent
family's whole layer under its public names (`latent_moe.decode_layer`,
`latent_moe.chunk_layer`: the absorbed decode walk or its kernel by
`latent_moe.fused_decode`'s rule, the chunk's form by
`latent_moe.expanded_attend`'s), told by the spec that the query has no
low-rank step (`q_rank` 0) and that nothing is turned (`roped` false).
From `llm/parts.py`: the norms, the products, the head, a layer's index
among its kind, the plain decode walk's work list. From `llm/experts.py`:
the MLP of a shared expert beside this chip's share of the routed ones
(`shared_mlp`), whose router scores by sigmoid under this family's spec.

The layer, for input x, two RMSNorms (`norm_eps`): ``h = x + Mix(N1(x))``,
``y = h + MLP(N2(h))``; ``x0 = E[ids]``; after the last layer a final norm
and the untied head. No biases but `dt_bias`.

A KDA layer (H = `lin_heads` heads of d = `head_dim`; u the normed input):

- ``q, k, v = SiLU(conv(u Wq)), SiLU(conv(u Wk)), SiLU(conv(u Wv))``
  (`wqkv`: the three side by side, H d wide each). ``conv`` is depthwise
  and causal over `conv_kernel` tokens, ``y_t = sum_i w[i] * x_(t - K + 1 +
  i)``, zeros before the sequence's first token: a sequence carries the
  last K - 1 inputs x, its *tail*, in its slot of the by-slot row pool
  ``(KDA layers, slots, 1, (K - 1) 3 H d)``, oldest first, in the compute
  type: one row a slot, so that a slot is a sublane of the pool's tiles
  and a row's gather or write moves no other (as ``(K - 1, 3 H d)`` a slot
  the compiler re-laid the whole 29 MB pool, 3 of a bfloat16 tile's 16
  rows, around every layer's gather: the compiled text, PERF.md PR 45).
- a head's q and k are L2-normed over d, q times ``d^-1/2``; float32 from
  here to the state.
- the decay a channel: ``g_t = -exp(a_log_h) * softplus((u Wfa) Wfb +
  dt_bias)`` (g <= 0), ``a_t = exp(g_t)``; ``beta_t = sigmoid(u Wb)`` a head.
- the state S (d x d a head, float32, in the sequence's slot of the state
  pool ``(KDA layers, slots, H, d, d)``; zero at a sequence's start):
  ``S' = Diag(a_t) S_(t-1)``, ``w_t = beta_t (v_t - S'^T k_t)``, ``S_t = S'
  + k_t w_t^T``, ``o_t = S_t^T q_t``: the state is read before it is
  written.
- ``y = Wo (RMSNorm(o_t; o_norm) * sigmoid((u Wga) Wgb))``, the norm a head
  with one weight of d.

Decode (`_decode_kda`): a row's tail is gathered from its slot, advanced
by one token and written back. Its state goes one of two ways, by
`fused_state`'s rule from the backend and the head's width alone: through
one kernel a layer (`pallas_state.delta_decode_update`: a program a row
and group of heads reads the state through the row's slot, advances it in
fast memory and writes it back where it lay, the pool aliased input to
output, so the states cross the memory once each way), or, for every
other shape, gathered by slot into a copy, advanced by `delta_step` and
scattered back (about nine passes over the rows' states), which stays the
definition the closed form, the kernel and the tests are held to. (A
third way, each slot of the pool advanced where it lies by XLA, the
slots without a row by g = 0 and beta = 0, was 8 % faster than the
gathered one at 17 rows and served tokens that were not the reference's
at a full bucket of 64, though 8 rows of 9 slots agreed to the last bit
on the chip: PERF.md section 6, PR 45. It is out of the program.)

Chunk (`delta_chunk`): the chunk's tokens in runs of `RUN` or fewer,
through the recurrence's closed form. With ``G_i`` the running sum of g
inside a run and ``S_0`` the state entering it: ``A_ij = sum_c k_ic k_jc
exp(G_ic - G_jc)`` (j < i), ``B_ij`` the same with q_i (j <= i); W solves
the unit lower-triangular ``(I + Diag(beta) A) W = Diag(beta) (V - (K *
exp(G)) S_0)``, by forward substitution for all runs and heads at once (it
does not read the state); ``O = (Q * exp(G)) S_0 + B W``; ``S_n =
Diag(exp(G_n)) S_0 + sum_j (k_j * exp(G_n - G_j)) w_j^T``, and `lax.scan`
hands S from run to run. Every exponent is a difference ``G_i - G_j <= 0``
with i >= j: nothing is divided by a running decay. A padding token (past
the chunk's last real one) has ``g = 0`` and ``beta = 0``: it leaves the
state alone, and the tail handed on is the last K - 1 *real* inputs. A
chunk at position 0 starts from a zero state and zero tails, whatever the
slot held. The state and every product that feeds it are float32 at
`PRECISION` whatever the compute type: the state carries a whole sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import pallas_state
from nnstreamer_tpu.llm import latent_moe, parts
from nnstreamer_tpu.llm.experts import shared_mlp
from nnstreamer_tpu.llm.parts import finish, layer_index, norm, proj
from nnstreamer_tpu.llm.spec import KDA, LMSpec
from nnstreamer_tpu.models.transformer import rmsnorm

_F32 = jnp.float32

# Tokens of a chunk the delta rule takes at a time: a run's (heads, run,
# run) systems are solved row by row for all runs at once, and its (run,
# run, heads, d) decays are 67 MB at 32 heads of 128.
RUN = 64
# The closed form's products, float32 operands: a float32 product on the
# matrix unit is one only at `highest` (the default rounds its operands to
# bfloat16), and these feed a state that carries the whole sequence.
PRECISION = jax.lax.Precision.HIGHEST
# what an L2 norm adds under its root
_L2_EPS = 1e-6


# -- the KDA layer's inputs ---------------------------------------------------

def conv_act(seq, w, c: int, dtype):
    """The causal depthwise convolution and SiLU: seq (..., c + K - 1, W)
    the tail then `c` inputs, w (K, W). Returns (..., c, W) in `dtype`:
    ``y_t = sum_i w[i] * seq[t + i]``, summed in float32."""
    y = sum(seq[..., i:i + c, :].astype(_F32) * w[i].astype(_F32)
            for i in range(w.shape[0]))
    return jax.nn.silu(y).astype(dtype)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _L2_EPS)


def kda_inputs(blk, u, qkv, live, spec: LMSpec, dtype):
    """What the delta rule takes, float32: q, k, v (N, H, d) from the
    convolved `qkv` (N, 3 H d), q and k normed a head and q scaled; the
    decay's logarithm g (N, H, d) <= 0 and beta (N, H) from the normed
    input u (N, 1, D). Where `live` (N,) is given, a token that is not
    live has g = 0 and beta = 0."""
    n = u.shape[0]
    h, d = spec.lin_heads, spec.head_dim
    q, k, v = (qkv[:, i * h * d:(i + 1) * h * d].reshape(n, h, d)
               .astype(_F32) for i in range(3))
    a = proj(blk, "wfb", proj(blk, "wfa", u, dtype), dtype)
    g = -jnp.exp(blk["a_log"].astype(_F32))[None, :, None] * jax.nn.softplus(
        a.astype(_F32).reshape(n, h, d)
        + blk["dt_bias"].astype(_F32).reshape(h, d))
    beta = jax.nn.sigmoid(proj(blk, "wb", u, dtype)[:, 0].astype(_F32))
    if live is not None:
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    return _l2(q) * d ** -0.5, _l2(k), v, g, beta


def _kda_out(blk, x, u, o, spec: LMSpec, dtype):
    """x + Wo (RMSNorm_head(o) * sigmoid((u Wga) Wgb)) for the delta
    rule's outputs o (N, H, d) float32."""
    n, h, d = o.shape
    gate = proj(blk, "wgb", proj(blk, "wga", u, dtype), dtype)
    o = rmsnorm(o, blk["o_norm"].astype(_F32), spec.norm_eps) \
        * jax.nn.sigmoid(gate.astype(_F32).reshape(n, h, d))
    return x + proj(blk, "wo", o.reshape(n, 1, h * d).astype(dtype), dtype)


# -- the delta rule ------------------------------------------------------------

def delta_step(q, k, v, g, beta, state):
    """One token a row: q, k, g (B, H, d), v (B, H, dv), beta (B, H),
    state (B, H, d, dv) float32. Returns (o (B, H, dv), the state after).
    The decayed state is read twice (for what it holds of k, and of q)
    and written once: ``o = S'^T q + (q . k) w``."""
    decayed = jnp.exp(g)[..., None] * state
    held = jnp.sum(k[..., None] * decayed, axis=-2)
    seen = jnp.sum(q[..., None] * decayed, axis=-2)
    w = beta[..., None] * (v - held)
    o = seen + jnp.sum(q * k, axis=-1, keepdims=True) * w
    return o, decayed + k[..., None] * w[..., None, :]


def _solve_rows(low, beta):
    """``T = (I + low)^-1 Diag(beta)`` for strictly lower-triangular low
    (..., n, n) and beta (..., n), by forward substitution: row i is
    ``beta_i e_i - low_i T``, the rows before it final and the others
    still zero. All the leading dims at once; n steps."""
    n = low.shape[-1]
    eye = jnp.eye(n, dtype=low.dtype)

    def row(i, t):
        r = beta[..., i, None] * eye[i] - jnp.einsum(
            "...j,...jk->...k", low[..., i, :], t, precision=PRECISION)
        return jax.lax.dynamic_update_index_in_dim(t, r, i, -2)

    return jax.lax.fori_loop(0, n, row, jnp.zeros_like(low))


def delta_chunk(q, k, v, g, beta, state, run: int = RUN):
    """A chunk's delta rule in the closed form (module docstring): q, k,
    g (C, H, d), v (C, H, dv), beta (C, H), all float32, a padding token's
    g and beta zero; state (H, d, dv) before the chunk. Runs of `run`
    tokens, the last filled up with padding tokens. Returns (o (C, H, dv),
    the state after the chunk)."""
    c, h, d = q.shape
    n = min(run, c)
    fill = -c % n
    if fill:
        q, k, v, g, beta = (jnp.pad(x, ((0, fill),) + ((0, 0),) * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    runs = (c + fill) // n
    q, k, v, g, beta = (x.reshape((runs, n) + x.shape[1:])
                        for x in (q, k, v, g, beta))
    gsum = jnp.cumsum(g, axis=1)                             # (R, n, H, d)
    ahead = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]   # j < i

    def within(xs):
        """A run's A (j < i) and B (j <= i), (H, n, n)."""
        qr, kr, gr = xs
        # exponents of pairs j > i are positive and masked: held at 0
        e = jnp.exp(jnp.minimum(gr[:, None] - gr[None, :], 0.0))
        ke = kr[None, :] * e                                 # (n, n, H, d)
        a = jnp.sum(kr[:, None] * ke, axis=-1).transpose(2, 0, 1)
        b = jnp.sum(qr[:, None] * ke, axis=-1).transpose(2, 0, 1)
        return (jnp.where(ahead, a, 0.0),
                jnp.where(ahead | jnp.eye(n, dtype=bool), b, 0.0))

    a, b = jax.lax.map(within, (q, k, gsum))                 # (R, H, n, n)
    bt = beta.transpose(0, 2, 1)                             # (R, H, n)
    t = _solve_rows(bt[..., None] * a, bt)
    grow = jnp.exp(gsum)
    last = gsum[:, -1]                                       # (R, H, d)
    k_in, q_in = k * grow, q * grow
    k_out = k * jnp.exp(last[:, None] - gsum)

    def one_run(s, xs):
        k_in, q_in, k_out, v, t, b, decay = xs
        rhs = v - jnp.einsum("nhc,hcv->nhv", k_in, s, precision=PRECISION)
        w = jnp.einsum("hij,jhv->ihv", t, rhs, precision=PRECISION)
        o = jnp.einsum("nhc,hcv->nhv", q_in, s, precision=PRECISION) \
            + jnp.einsum("hij,jhv->ihv", b, w, precision=PRECISION)
        s = decay[..., None] * s + jnp.einsum(
            "nhc,nhv->hcv", k_out, w, precision=PRECISION)
        return s, o

    state, o = jax.lax.scan(one_run, state, (k_in, q_in, k_out, v, t, b,
                                             jnp.exp(last)))
    return o.reshape((runs * n,) + o.shape[2:])[:c], state


# -- decode -------------------------------------------------------------------

def fused_state(spec: LMSpec) -> bool:
    """Whether a decode step moves its rows' states through their slots in
    one kernel a layer (`pallas_state.delta_decode_update`) or gathers,
    advances (`delta_step`) and scatters them: from the backend and the
    head's width alone. The kernel takes a head's state as whole (8, 128)
    tiles with its rows down the sublanes."""
    return jax.default_backend() == "tpu" and spec.head_dim % 128 == 0


@functools.partial(jax.jit, static_argnames=("dense", "spec", "dtype"))
def _decode_kda(blk, x, li, live, slots, t_pool, s_pool, *, dense, spec,
                dtype):
    """KDA layer `li` (among the KDA ones) of a decode step (jitted with
    `li` an argument, so a step traces a layer of each shape once)."""
    u = norm(blk["ln1"], x, spec, dtype)
    b = x.shape[0]
    seq = jnp.concatenate(
        [t_pool[li, slots].astype(dtype).reshape(b, spec.conv_kernel - 1, -1),
         proj(blk, "wqkv", u, dtype)], axis=1)          # (B, K, 3 H d)
    t_pool = t_pool.at[li, slots].set(
        seq[:, 1:].reshape(b, 1, -1).astype(t_pool.dtype))
    qkv = conv_act(seq, blk["conv"], 1, dtype)[:, 0]
    q, k, v, g, beta = kda_inputs(blk, u, qkv, None, spec, dtype)
    if fused_state(spec):
        # each live row's state through its slot, once each way (the
        # live rows are the bucket's first: their count is the mask's)
        o, s_pool = pallas_state.delta_decode_update(
            q, k, v, jnp.exp(g), beta, s_pool, li, slots,
            jnp.sum(live, dtype=jnp.int32))
    else:
        # the rows' states gathered by slot, advanced, scattered back
        # (the module docstring has the record of the third way)
        o, state = delta_step(q, k, v, g, beta, s_pool[li, slots])
        s_pool = s_pool.at[li, slots].set(state)
    x = _kda_out(blk, x, u, o, spec, dtype)
    x, load = shared_mlp(blk, norm(blk["ln2"], x, spec, dtype), live, dense,
                         spec, dtype, onto=x)
    return x, load, t_pool, s_pool


def delta_moe_decode_step(params, cur, tables, pos, n_live, slots, k_pool,
                          i_pool, t_pool, s_pool, *, spec: LMSpec,
                          dtype=jnp.float32):
    """One decode step for a bucketed batch. cur, pos, slots (B_b,)
    int32: each row's token, position and slot (padding rows: the scratch
    slot and a table of the scratch block); tables (B_b, max_blocks)
    int32; n_live () int32, the real rows (the first ones). The pools:
    the latents and the shared keys by block (the LATENT layers'), the
    tails and the states by slot (the KDA layers'). Returns (logits (B_b,
    vocab) f32, the expert layers' counts (layers, experts_held + 1)
    int32, k_pool, i_pool, t_pool, s_pool)."""
    b = cur.shape[0]
    bs = k_pool.shape[2]
    write_blk = tables[jnp.arange(b), pos // bs]
    write_off = pos % bs
    live = jnp.arange(b) < n_live
    # the latent layers' walk, as the latent family's step makes it
    if latent_moe.fused_decode(bs, spec, k_pool.dtype):
        walk, t = (tables, n_live), 0
    else:
        nb_c, n_chunks, t = latent_moe.walk_plan(bs, b, tables.shape[1])
        walk = parts.live_items(tables, pos, bs, nb_c, n_chunks, t)
    x = params["embed"][cur][:, None, :].astype(dtype)
    load = []
    kinds = spec.layer_kinds
    for i, (kind, li, blk) in enumerate(zip(kinds, layer_index(kinds),
                                            params["blocks"])):
        dense = i < spec.dense_layers
        if kind == KDA:
            x, counts, t_pool, s_pool = _decode_kda(
                blk, x, li, live, slots, t_pool, s_pool, dense=dense,
                spec=spec, dtype=dtype)
        else:
            x, counts, k_pool, i_pool = latent_moe.decode_layer(
                blk, x, li, pos, live, write_blk, write_off, walk, k_pool,
                i_pool, dense=dense, t=t, spec=spec, dtype=dtype)
        if counts is not None:
            load.append(counts)
    return (finish(params, x[:, 0], dtype, spec.norm_eps), jnp.stack(load),
            k_pool, i_pool, t_pool, s_pool)


# -- chunk prefill ------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dense", "run", "spec", "dtype"))
def _chunk_kda(blk, x, li, live, last_idx, fresh, slot, t_pool, s_pool, *,
               dense, run, spec, dtype):
    """KDA layer `li` (among the KDA ones) of a chunk: x (C, 1, D), the
    chunk's tokens as rows; `fresh`: the chunk is its sequence's first;
    `run`: the tokens the closed form takes at a time."""
    c = x.shape[0]
    u = norm(blk["ln1"], x, spec, dtype)
    tail = jnp.where(fresh, 0, t_pool[li, slot]).astype(dtype).reshape(
        spec.conv_kernel - 1, -1)
    seq = jnp.concatenate([tail, proj(blk, "wqkv", u, dtype)[:, 0]], axis=0)
    # the last K - 1 real inputs: the chunk's own from `last_idx` back,
    # and the tail it came with where it has fewer
    t_pool = t_pool.at[li, slot].set(jax.lax.dynamic_slice_in_dim(
        seq, last_idx + 1, tail.shape[0]).reshape(1, -1).astype(t_pool.dtype))
    qkv = conv_act(seq, blk["conv"], c, dtype)
    o, state = delta_chunk(*kda_inputs(blk, u, qkv, live, spec, dtype),
                           jnp.where(fresh, 0.0, s_pool[li, slot]), run)
    s_pool = s_pool.at[li, slot].set(state)
    x = _kda_out(blk, x, u, o, spec, dtype)
    x, load = shared_mlp(blk, norm(blk["ln2"], x, spec, dtype), live, dense,
                         spec, dtype, onto=x)
    return x, load, t_pool, s_pool


def delta_moe_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table, slot,
                            k_pool, i_pool, t_pool, s_pool, last_idx, *,
                            spec: LMSpec, dtype=jnp.float32,
                            by_block: bool = False, fused: bool = False,
                            expanded: bool = False,
                            tile: int = parts.CTX_TILE, run: int = RUN):
    """One prompt chunk of one sequence: the arguments of
    `latent_moe_prefill_chunk` with the sequence's slot after its table
    and the tails' and states' pools after the two by block. A chunk at
    ``pos0 == 0`` starts the sequence's states and tails from zero. The
    static arguments are the latent layers' (`latent_moe.chunk_layer`)
    and `run`, the tokens the KDA layers' closed form takes at a time.
    Returns (last real token's logits (vocab,) f32, the expert layers'
    counts over the chunk's real tokens (layers, experts_held + 1) int32,
    k_pool, i_pool, t_pool, s_pool)."""
    c = ids.shape[1]
    pos = pos0 + jnp.arange(c)
    live = jnp.arange(c) <= last_idx
    x = params["embed"][ids[0]][:, None, :].astype(dtype)
    load = []
    kinds = spec.layer_kinds
    for i, (kind, li, blk) in enumerate(zip(kinds, layer_index(kinds),
                                            params["blocks"])):
        dense = i < spec.dense_layers
        if kind == KDA:
            x, counts, t_pool, s_pool = _chunk_kda(
                blk, x, li, live, last_idx, pos0 == 0, slot, t_pool, s_pool,
                dense=dense, run=run, spec=spec, dtype=dtype)
        else:
            x, counts, k_pool, i_pool = latent_moe.chunk_layer(
                blk, x, li, pos, live, blk_idx, blk_off, table, k_pool,
                i_pool, dense=dense, tile=tile, by_block=by_block,
                fused=fused, expanded=expanded, spec=spec, dtype=dtype)
        if counts is not None:
            load.append(counts)
    logits = finish(params, x[last_idx, 0][None, :], dtype,
                    spec.norm_eps)[0]
    return logits, jnp.stack(load), k_pool, i_pool, t_pool, s_pool
