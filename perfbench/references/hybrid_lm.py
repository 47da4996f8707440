"""Plain reference of the decoder whose layers are linear attention with a
carried state or block-sparse attention over compressed keys
(openbmb/MiniCPM-SALA): float32 at ``precision="highest"``, no cache, no
state handed on, no code of the program.  It also makes the seeded weights
the program is handed.

``x`` is a token's residual (hidden D), eps from the config, no projection
has a bias.  ``r = scale_depth / sqrt(published depth)``, whatever depth the
file keeps.  Embedding: ``x = scale_emb * E[id]``.  Each layer:
``x += r * mixer(RMSNorm(x; ln1))``, then ``x += r * Wd (silu(Wg h) * Wu h)``
with ``h = RMSNorm(x; ln2)``.  Head: ``logits = W (RMSNorm(x; ln_f) /
(hidden_size / dim_model_base))``, untied.

*Linear layer* (``lightning-attn``; ``lightning_nh`` heads of
``lightning_head_dim``): ``q, k, v = h Wq, h Wk, h Wv``; per-head RMSNorm on
q and k; rope on all dims of q and k, base ``rope_theta``, half-split.  Head
j of H decays by ``lam_j = exp(-2^(-8 (j + 1) / H))``:
``S_t = lam_j S_{t-1} + k_t^T v_t`` (``S_{-1} = 0``),
``o_t = (q_t / sqrt(hd)) S_t``, which is
``o_t = sum_{s <= t} lam_j^(t - s) (q_t . k_s / sqrt(hd)) v_s``: the masked
quadratic form computed here (`linear_attention`); `linear_recurrence` is
the same thing token by token, and the tests hold one to the other.
``y = Wo (sigmoid(h Wgate) * RMSNorm_{H*hd}(o; o_norm))``.

*Sparse layer* (``minicpm4``; H query heads, G key/value heads of hd, no
rope): q and k with per-head RMSNorm, v.  Compressed key ``c_m`` of a KV head:
the mean of ``k_{stride*m} ... k_{stride*m + kernel - 1}``, defined once its
last token exists.  For query t and KV head g:
``p_{t,m} = sum over the H/G query heads of g of
softmax_m(q_t . c_m / sqrt(hd))`` over the defined m.  The score of block b
(tokens ``block*b ... block*b + block - 1``) is the largest ``p_{t,m}`` over
the compressed keys whose tokens overlap it; the first ``init_blocks`` blocks
and the ``window / block`` blocks ending in the query's own score infinity;
the query attends the ``topk`` highest blocks (ties to the lower index, the
forced ones counted among them), causally:
``o_{t,h} = softmax over the selected s <= t of (q_{t,h} . k_{s,g} /
sqrt(hd)) v_{s,g}``; ``y = Wo (sigmoid(h Wgate) * o)``.  While a sequence
has at most ``topk`` blocks every block is selected: dense causal attention.
The family's switch to dense attention for whole sequences under a
``dense_len`` is not reproduced (one rule for every position).

Parameter layout (the hand-over format of this family's ``tensor_llm``
bundles): ``embed (V, D)``; a linear ``blocks[i] = {ln1 (D), wqkv (D,
3*H*hd) = [q | k | v], q_norm (hd), k_norm (hd), wg (D, H*hd), o_norm
(H*hd), wo (H*hd, D), ln2 (D), wi (D, 2*F) = [gate | up], wd (F, D)}``; a
sparse one the same without ``o_norm`` and with ``wqkv (D, H*hd + 2*G*hd)``;
``ln_f (D)``, ``head (D, V)``.

How it is computed, so that a 66 k-token request fits beside the weights:
layer by layer over the whole sequence; projections and the MLP in blocks of
tokens, the linear layer's heads in groups, both attentions in blocks of
``q_block`` queries against every key behind their masks (the block
selection as a mask over dense scores).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references.decoder_lm import key_from_seed
from perfbench.references.sparse_moe_lm import _matmul, _rmsnorm, _rope

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
TOKEN_BLOCK = 4096      # tokens a projection or the MLP takes at a time
HEAD_GROUP = 8          # linear heads projected and attended together
LINEAR, SPARSE = "linear", "sparse"
KINDS = {"lightning-attn": LINEAR, "minicpm4": SPARSE}


def dims(cfg: dict) -> dict:
    a = cfg["assumed_sizes"]
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
            "lh": int(cfg["lightning_nh"]),
            "lhd": int(cfg["lightning_head_dim"]),
            "f": int(cfg["intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "kinds": tuple(KINDS[t] for t in cfg["mixer_types"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "emb_scale": float(cfg["scale_emb"]),
            "r": float(cfg["scale_depth"]) / math.sqrt(
                int(cfg["published"]["num_hidden_layers"])),
            "logit_div": cfg["hidden_size"] / cfg["dim_model_base"],
            "kernel": int(a["sparse_kernel_size"]),
            "stride": int(a["sparse_kernel_stride"]),
            "block": int(a["sparse_block_size"]),
            "topk": int(a["sparse_topk"]),
            "window": int(a["sparse_window_size"]),
            "init": int(a["sparse_init_blocks"])}


def param_count(cfg: dict) -> dict:
    """Parameters of a layer of each kind, by part, and of what lies
    outside the layers (embedding and head; the final norm apart)."""
    m = dims(cfg)
    qw, kw, lw = m["h"] * m["hd"], m["hkv"] * m["hd"], m["lh"] * m["lhd"]
    mlp = 3 * m["d"] * m["f"]
    sparse = {"attention": m["d"] * (qw + 2 * kw) + qw * m["d"],
              "gate": m["d"] * qw, "mlp": mlp,
              "norms": 2 * m["d"] + 2 * m["hd"]}
    linear = {"attention": m["d"] * 3 * lw + lw * m["d"],
              "gate": m["d"] * lw, "mlp": mlp,
              "norms": 2 * m["d"] + 2 * m["lhd"] + lw}
    return {SPARSE: sparse, LINEAR: linear,
            "per_layer": {SPARSE: sum(sparse.values()),
                          LINEAR: sum(linear.values())},
            "outside": 2 * m["vocab"] * m["d"], "final_norm": m["d"]}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device, already in the type they are served
    in: one jitted call a layer and one for what lies outside."""
    m = dims(cfg)

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, F32, -lim, lim).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(key, kind):
        k = jax.random.split(key, 5)
        if kind == LINEAR:
            aw, hd = m["lh"] * m["lhd"], m["lhd"]
            qkv = 3 * aw
        else:
            aw, hd = m["h"] * m["hd"], m["hd"]
            qkv = aw + 2 * m["hkv"] * m["hd"]
        out = {"ln1": jnp.ones((m["d"],), dtype),
               "wqkv": xavier(k[0], (m["d"], qkv)),
               "q_norm": jnp.ones((hd,), dtype),
               "k_norm": jnp.ones((hd,), dtype),
               "wg": xavier(k[1], (m["d"], aw)),
               "wo": xavier(k[2], (aw, m["d"])),
               "ln2": jnp.ones((m["d"],), dtype),
               "wi": xavier(k[3], (m["d"], 2 * m["f"])),
               "wd": xavier(k[4], (m["f"], m["d"]))}
        if kind == LINEAR:
            out["o_norm"] = jnp.ones((aw,), dtype)
        return out

    @jax.jit
    def outside(key):
        k = jax.random.split(key, 2)
        return {"embed": xavier(k[0], (m["vocab"], m["d"])),
                "ln_f": jnp.ones((m["d"],), dtype),
                "head": xavier(k[1], (m["d"], m["vocab"]))}

    keys = jax.random.split(key_from_seed(seed), m["layers"] + 1)
    out = outside(keys[-1])
    out["blocks"] = [layer(keys[i], kind)
                     for i, kind in enumerate(m["kinds"])]
    return out


# -- the two mixers -------------------------------------------------------------

def decay_slopes(n_heads: int):
    """Head j of H forgets by exp(-slope_j) a token,
    slope_j = 2^(-8 (j + 1) / H)."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=F32) / n_heads)


def linear_recurrence(q, k, v, slopes):
    """q, k, v (S, H, hd), slopes (H,) -> o (S, H, hd): the state, one
    token at a time."""
    lam = jnp.exp(-slopes)[:, None, None]
    hd = q.shape[-1]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hde->he", qt * hd ** -0.5, state,
                                 precision=HIGHEST)

    zero = jnp.zeros((q.shape[1], hd, v.shape[-1]), F32)
    return jax.lax.scan(step, zero, (q, k, v))[1]


def linear_attention(q, k, v, slopes, q_block: int):
    """The same as `linear_recurrence`, as the masked quadratic form, in
    blocks of `q_block` queries (S a multiple of it)."""
    s, _, hd = q.shape
    kpos = jnp.arange(s)

    def block(i):
        at = i * q_block
        qb = jax.lax.dynamic_slice_in_dim(q, at, q_block)
        dist = (at + jnp.arange(q_block))[:, None] - kpos[None, :]
        decay = jnp.where(dist >= 0, jnp.exp(
            -slopes[:, None, None] * jnp.maximum(dist, 0)[None]), 0.0)
        sc = jnp.einsum("qhd,shd->hqs", qb, k,
                        precision=HIGHEST) * hd ** -0.5 * decay
        return jnp.einsum("hqs,shd->qhd", sc, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // q_block))
    return out.reshape(s, q.shape[1], v.shape[-1])


def compressed_keys(k, kernel: int, stride: int):
    """k (S, G, hd), S a multiple of stride -> (S / stride, G, hd):
    entry m is the mean of k[stride*m : stride*m + kernel] (the last
    ones, whose tokens run past S, are never defined for a query)."""
    s, g, hd = k.shape
    sums = jnp.sum(k.reshape(s // stride, stride, g, hd), axis=1)
    r = kernel // stride
    sums = jnp.pad(sums, ((0, r - 1), (0, 0), (0, 0)))
    return sum(sums[i:i + s // stride] for i in range(r)) / kernel


def block_scores(p, *, kernel: int, stride: int, block: int):
    """p (..., M) per compressed key (-1 where not defined) -> (..., M *
    stride / block): each block's largest p over the keys whose tokens
    overlap it."""
    per = block // stride               # keys that start inside a block
    extra = (kernel - 1) // stride      # earlier keys that reach into it
    n = p.shape[-1] // per
    padded = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(extra, per)],
                     constant_values=-1.0)
    return functools.reduce(jnp.maximum, (
        padded[..., i::per][..., :n] for i in range(per + extra)))


def select_blocks(score, qpos, *, block: int, topk: int, window: int,
                  init: int):
    """score (Q, G, NB) of queries at positions qpos (Q,) -> (Q, G, NB)
    bool: the `topk` highest blocks a query, the first `init` and the
    window's forced, ties to the lower index; a block past the query's
    own is never selected."""
    nb = score.shape[-1]
    b = jnp.arange(nb)[None, :]
    own = (qpos // block)[:, None]
    forced = (b < init) | (b > own - window // block)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((b <= own)[:, None, :], score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(topk, nb))
    sel = jnp.zeros(score.shape, bool)
    sel = jnp.put_along_axis(sel, idx, True, axis=-1, inplace=False)
    return sel & (b <= own)[:, None, :]


def sparse_attention(q, k, v, m: dict, q_block: int):
    """q (S, H, hd), k and v (S, G, hd) -> (o (S, H, hd), positions each
    query attends by KV head (S, G))."""
    s, h, hd = q.shape
    g = k.shape[1]
    kernel, stride, block = m["kernel"], m["stride"], m["block"]
    ck = compressed_keys(k, kernel, stride)                    # (M, G, hd)
    ck_last = stride * jnp.arange(ck.shape[0]) + kernel - 1
    qg = q.reshape(s, g, h // g, hd)
    kpos = jnp.arange(s)

    def blocked(i):
        at = i * q_block
        qpos = at + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(qg, at, q_block)
        defined = ck_last[None, :] <= qpos[:, None]            # (Q, M)
        sc = jnp.einsum("qgrd,mgd->qgrm", qb, ck,
                        precision=HIGHEST) * hd ** -0.5
        sc = jnp.where(defined[:, None, None, :], sc, -jnp.inf)
        top = jnp.max(sc, axis=-1, keepdims=True)
        e = jnp.where(defined[:, None, None, :],
                      jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0)),
                      0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        p = jnp.where(defined[:, None, :], jnp.sum(p, axis=2), -1.0)
        sel = select_blocks(
            block_scores(p, kernel=kernel, stride=stride, block=block),
            qpos, block=block, topk=m["topk"], window=m["window"],
            init=m["init"])                                    # (Q, G, NB)
        may = jnp.repeat(sel, block, axis=-1) & (
            kpos[None, :] <= qpos[:, None])[:, None, :]        # (Q, G, S)
        att = jnp.einsum("qgrd,sgd->qgrs", qb, k,
                         precision=HIGHEST) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(may[:, :, None, :], att, -jnp.inf),
                           axis=-1)
        return (jnp.einsum("qgrs,sgd->qgrd", w, v, precision=HIGHEST),
                jnp.sum(may, axis=-1))

    o, n = jax.lax.map(blocked, jnp.arange(s // q_block))
    return o.reshape(s, h, hd), n.reshape(s, g)


# -- the forward pass ----------------------------------------------------------

def _by_tokens(fn, x):
    """fn over x (S, ...) in blocks of TOKEN_BLOCK tokens."""
    s = x.shape[0]
    tb = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    out = jax.lax.map(fn, x.reshape((s // tb, tb) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


def _mlp(x, blk, m, quant):
    f = m["f"]

    def one(xb):
        gu = _matmul(_rmsnorm(xb, blk["ln2"], m["eps"]), blk["wi"], quant)
        return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], blk["wd"], quant)

    return x + m["r"] * _by_tokens(one, x)


def _gated_out(x, att, blk, m, quant, norm):
    """x + r * Wo (sigmoid(h Wgate) * att), att normed first where the
    layer has an output norm; h recomputed, in blocks of tokens."""
    def one(xa):
        xb, ab = xa
        gate = jax.nn.sigmoid(_matmul(
            _rmsnorm(xb, blk["ln1"], m["eps"]), blk["wg"], quant))
        if norm:
            ab = _rmsnorm(ab, blk["o_norm"], m["eps"])
        return _matmul(gate * ab, blk["wo"], quant)

    s = x.shape[0]
    tb = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    y = jax.lax.map(one, (x.reshape(s // tb, tb, -1),
                          att.reshape(s // tb, tb, -1)))
    return x + m["r"] * y.reshape(s, -1)


def _static(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()))


@functools.partial(jax.jit, static_argnames=("ms", "quant", "q_block"))
def _linear_layer(x, blk, *, ms, quant, q_block):
    m = dict(ms)
    s = x.shape[0]
    nh, hd = m["lh"], m["lhd"]
    aw = nh * hd
    slopes = decay_slopes(nh)
    grp = min(HEAD_GROUP, nh)
    outs = []
    for h0 in range(0, nh, grp):
        cols = slice(h0 * hd, (h0 + grp) * hd)

        def proj(xb, at, cols=cols):
            w = blk["wqkv"][:, at:at + aw][:, cols]
            return _matmul(_rmsnorm(xb, blk["ln1"], m["eps"]), w, quant)

        q, k, v = (_by_tokens(functools.partial(proj, at=at), x)
                   .reshape(s, grp, hd) for at in (0, aw, 2 * aw))
        q = _rope(_rmsnorm(q, blk["q_norm"], m["eps"]), m["theta"])
        k = _rope(_rmsnorm(k, blk["k_norm"], m["eps"]), m["theta"])
        outs.append(linear_attention(q, k, v, slopes[h0:h0 + grp], q_block)
                    .reshape(s, grp * hd))
    x = _gated_out(x, jnp.concatenate(outs, axis=-1), blk, m, quant, True)
    return _mlp(x, blk, m, quant)


@functools.partial(jax.jit, static_argnames=("ms", "quant", "q_block"))
def _sparse_layer(x, blk, *, ms, quant, q_block):
    m = dict(ms)
    s = x.shape[0]
    h, g, hd = m["h"], m["hkv"], m["hd"]
    qw, kw = h * hd, g * hd

    def proj(xb):
        return _matmul(_rmsnorm(xb, blk["ln1"], m["eps"]), blk["wqkv"],
                       quant)

    qkv = _by_tokens(proj, x)
    q = _rmsnorm(qkv[:, :qw].reshape(s, h, hd), blk["q_norm"], m["eps"])
    k = _rmsnorm(qkv[:, qw:qw + kw].reshape(s, g, hd), blk["k_norm"],
                 m["eps"])
    v = qkv[:, qw + kw:].reshape(s, g, hd)
    o, n = sparse_attention(q, k, v, m, q_block)
    x = _gated_out(x, o.reshape(s, qw), blk, m, quant, False)
    return _mlp(x, blk, m, quant), n


@functools.partial(jax.jit, static_argnames=("eps", "div", "quant"))
def _head(x, ln_f, head, *, eps, div, quant):
    return _matmul(_rmsnorm(x, ln_f, eps) / div, head, quant)


def forward_logits(params, cfg: dict, ids, *, quant=None, pad_to: int = 4096,
                   q_block: int = 64, rows=None, taps=None):
    """ids (S,) int -> logits (S, vocab) float32 (only positions `rows`,
    a slice, where given).  The sequence is padded on the right to a
    multiple of `pad_to` (both mixers are causal, so padding stays out of
    the real positions).  `taps`, a dict, receives "attended" (sparse
    layers, S, G): the positions each query attends, for the tests of
    the program's counts."""
    m = dims(cfg)
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    unit = math.lcm(q_block, m["block"])
    pad_to = max(unit, min(pad_to, -(-s // unit) * unit))
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(F32) * m["emb_scale"]
    ms = _static({k: v for k, v in m.items() if k != "kinds"})
    attended = []
    for kind, blk in zip(m["kinds"], params["blocks"]):
        if kind == LINEAR:
            x = _linear_layer(x, blk, ms=ms, quant=quant, q_block=q_block)
        else:
            x, n = _sparse_layer(x, blk, ms=ms, quant=quant,
                                 q_block=q_block)
            attended.append(n[:s])
    if taps is not None:
        taps["attended"] = np.asarray(jnp.stack(attended))
    x = x[:s] if rows is None else x[:s][rows]
    return _head(x, params["ln_f"], params["head"], eps=m["eps"],
                 div=m["logit_div"], quant=quant)


def served_token_gaps(params, cfg, prompt, served, *, quants=()):
    """For one finished request: at each served position, how far the
    token lies below the reference's best logit.

    Returns (gaps of the `served` tokens, {quant: gaps of the tokens the
    `quant` forward puts first, teacher-forced over the same prompt and
    tokens}), each (n,) float32.
    """
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    n, p = served.shape[0], prompt.shape[0]
    ids = np.concatenate([prompt, served[:-1]])
    rows = slice(p - 1, p - 1 + n)
    ref = forward_logits(params, cfg, ids, rows=rows)
    best = jnp.max(ref, axis=-1)

    def below_best(tokens):
        return np.asarray(best - jnp.take_along_axis(
            ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0])

    low = {}
    for quant in quants:
        logits = forward_logits(params, cfg, ids, quant=quant, rows=rows)
        low[quant] = below_best(jnp.argmax(logits, axis=-1))
    return below_best(served), low
