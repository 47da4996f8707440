"""Plain reference of the sparse-expert decoder whose attention a learned
indexer chooses (the language model of Kwai-Keye/Keye-VL-2.0-30B-A3B):
float32 at ``precision="highest"``, no cache, no batching, no code of the
program.  It also makes the seeded weights the program is handed.

The layer, for input x (hidden D) at position t, eps from the config, no
projection has a bias:

1. ``h = RMSNorm(x; ln1)``; ``q = h Wq`` as H heads of hd, ``k = h Wk`` and
   ``v = h Wv`` as Hkv heads of hd.
2. ``q = RMSNorm_hd(q; q_norm)``, ``k = RMSNorm_hd(k; k_norm)``, per head.
3. Rope on all hd dims of q and k, base ``rope_theta``, the half-split
   rotation (text only: every M-RoPE section carries the same position).
4. Indexer: ``qI = h WqI`` as Hi heads of di, ``kI = h WkI`` (one head),
   ``w = h Ww`` (Hi values); rope on qI and kI, same base.
   ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for ``s <= t``.
   ``S_t`` = the ``min(topk, t + 1)`` positions of largest score, ties to
   the lower position (the rule of ``jax.lax.top_k``).
5. ``a = softmax_{s in S_t}(q . k_s / sqrt(hd)) v_s``, one ``S_t`` for all
   heads, H / Hkv query heads to a key/value head; ``x = x + a Wo``.
6. ``g = RMSNorm(x; ln2)``; router logits ``g Wr`` and their softmax; the
   ``experts_per_tok`` largest, renormalised to sum 1;
   ``y = sum_e p_e * (silu(g Wg_e) * (g Wu_e)) Wd_e``; no token is dropped;
   ``x = x + y``.
7. After the last layer ``RMSNorm(x; ln_f)`` and the untied head.

Parameter layout (the hand-over format of this family's ``tensor_llm``
bundles): ``embed (V, D)``, ``blocks[i] = {ln1 (D), wqkv (D, H*hd +
2*Hkv*hd) = [q | k | v], q_norm (hd), k_norm (hd), wo (H*hd, D),
widx (D, Hi*di + di + Hi) = [qI | kI | w], ln2 (D), router (D, E),
ewi (E, D, 2*F) = [gate | up], ewd (E, F, D)}``, ``ln_f (D)``,
``head (D, V)``.

How it is computed, so that a 33 k-token request fits beside the weights:
layer by layer over the whole sequence, the attention in blocks of
``q_block`` queries against every key behind the causal mask, the selection
as a mask (the k-th largest score of ``jax.lax.top_k`` as the threshold,
ties counted from the lowest position by a running sum), and the expert
layer over (token, expert) pairs sorted by expert through
``jax.lax.ragged_dot`` at float32 ``highest``; ``moe_dense`` is the same
layer with every expert on every token, which the tests hold it to.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import lowprec
from perfbench.references.decoder_lm import key_from_seed

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MOE_BLOCK = 4096        # tokens the expert layer takes at a time


def dims(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
            "hi": int(sa["indexer_num_heads"]), "di": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"]), "e": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "f": int(cfg["moe_intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def param_count(cfg: dict) -> dict:
    """Parameters of one layer, by part, and of what lies outside the
    layers (embedding and head; the final norm apart)."""
    m = dims(cfg)
    qw, kw = m["h"] * m["hd"], m["hkv"] * m["hd"]
    layer = {"attention": m["d"] * (qw + 2 * kw) + qw * m["d"],
             "indexer": m["d"] * (m["hi"] * m["di"] + m["di"] + m["hi"]),
             "router": m["d"] * m["e"],
             "experts": m["e"] * 3 * m["d"] * m["f"],
             "norms": 2 * m["d"] + 2 * m["hd"]}
    return {"layer": layer, "per_layer": sum(layer.values()),
            "outside": 2 * m["vocab"] * m["d"], "final_norm": m["d"]}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device, already in the type they are served
    in: one jitted call a layer and one for what lies outside (a layer's
    experts are 1.2 GB; their float32 draws do not pile up)."""
    m = dims(cfg)
    qw, kw = m["h"] * m["hd"], m["hkv"] * m["hd"]

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, F32, -lim, lim).astype(dtype)

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 6)
        return {"ln1": jnp.ones((m["d"],), dtype),
                "wqkv": xavier(k[0], (m["d"], qw + 2 * kw)),
                "q_norm": jnp.ones((m["hd"],), dtype),
                "k_norm": jnp.ones((m["hd"],), dtype),
                "wo": xavier(k[1], (qw, m["d"])),
                "widx": xavier(k[2], (m["d"],
                                      m["hi"] * m["di"] + m["di"] + m["hi"])),
                "ln2": jnp.ones((m["d"],), dtype),
                "router": xavier(k[3], (m["d"], m["e"])),
                "ewi": xavier(k[4], (m["e"], m["d"], 2 * m["f"])),
                "ewd": xavier(k[5], (m["e"], m["f"], m["d"]))}

    @jax.jit
    def outside(key):
        k = jax.random.split(key, 2)
        return {"embed": xavier(k[0], (m["vocab"], m["d"])),
                "ln_f": jnp.ones((m["d"],), dtype),
                "head": xavier(k[1], (m["d"], m["vocab"]))}

    keys = jax.random.split(key_from_seed(seed), m["layers"] + 1)
    out = outside(keys[-1])
    out["blocks"] = [layer(keys[i]) for i in range(m["layers"])]
    return out


# -- the forward pass ----------------------------------------------------------

def _fake(x, w, quant):
    """Activations by row, weights by output column (for experts: of
    each expert), in the control's lower precision."""
    if quant is None:
        return x, w
    return lowprec.fake(x, -1, quant), lowprec.fake(w, -2, quant)


def _matmul(x, w, quant):
    x, w = _fake(x, w.astype(F32), quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * w.astype(F32)


def _rope(x, theta):
    """x (S, H, hd): rotate halves by position, base `theta`."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def index_scores(qi, ki, w):
    """qi (Q, Hi, di), ki (S, di), w (Q, Hi) -> I (Q, S)."""
    s = jnp.einsum("qjd,sd->qjs", qi, ki, precision=HIGHEST)
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1)


def selection_mask(scores, qpos, topk):
    """scores (Q, S) of queries at positions qpos (Q,) -> (Q, S) bool:
    for each query the min(topk, t + 1) positions s <= t of largest
    score, ties to the lower position."""
    q, s = scores.shape
    may = jnp.arange(s)[None, :] <= qpos[:, None]
    scores = jnp.where(scores == 0.0, 0.0, scores)     # -0.0 ties +0.0
    masked = jnp.where(may, scores, -jnp.inf)
    k_eff = jnp.minimum(min(int(topk), s), qpos + 1)
    best, _ = jax.lax.top_k(masked, min(int(topk), s))
    thr = jnp.take_along_axis(best, (k_eff - 1)[:, None], axis=1)
    above = masked > thr
    tie = may & (masked == thr)
    need = k_eff - jnp.sum(above, axis=1)
    return above | (tie & (jnp.cumsum(tie, axis=1) <= need[:, None]))


def _route(g, router, k, quant):
    """g (S, D) -> (renormalised weights (S, k), experts (S, k))."""
    probs = jax.nn.softmax(_matmul(g, router, quant), axis=-1)
    p, e = jax.lax.top_k(probs, k)
    return p / jnp.sum(p, axis=-1, keepdims=True), e


def moe_dense(g, blk, k, quant=None):
    """The expert layer with every expert computed on every token and
    combined by a weight that is 0 off a token's own experts."""
    ne = blk["router"].shape[1]
    f = blk["ewd"].shape[1]
    p, e = _route(g, blk["router"], k, quant)
    gate = jnp.sum(p[:, :, None] * (e[:, :, None] == jnp.arange(ne)), axis=1)
    y = jnp.zeros_like(g)
    for i in range(ne):
        gu = _matmul(g, blk["ewi"][i], quant)
        y += gate[:, i:i + 1] * _matmul(
            jax.nn.silu(gu[:, :f]) * gu[:, f:], blk["ewd"][i], quant)
    return y


def moe(g, blk, k, quant=None):
    """The expert layer over (token, expert) pairs sorted by expert.
    Returns (y (S, D), the experts of each token (S, k))."""
    s, d = g.shape
    ne = blk["router"].shape[1]
    f = blk["ewd"].shape[1]
    p, e = _route(g, blk["router"], k, quant)
    flat = e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(flat[:, None] == jnp.arange(ne)[None, :], axis=0,
                    dtype=jnp.int32)
    xs, wi = _fake(g[order // k], blk["ewi"].astype(F32), quant)
    gu = jax.lax.ragged_dot(xs, wi, sizes, precision=HIGHEST,
                            preferred_element_type=F32)
    mid, wd = _fake(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                    blk["ewd"].astype(F32), quant)
    out = jax.lax.ragged_dot(mid, wd, sizes, precision=HIGHEST,
                             preferred_element_type=F32)
    back = jnp.argsort(order)
    return jnp.sum(out[back].reshape(s, k, d) * p[:, :, None], axis=1), e


@functools.partial(jax.jit, static_argnames=(
    "h", "hkv", "hd", "hi", "di", "topk", "k", "eps", "theta", "quant",
    "q_block"))
def _layer(x, blk, *, h, hkv, hd, hi, di, topk, k, eps, theta, quant,
           q_block):
    """x (S, D), S a multiple of q_block.  Returns (x, the experts of
    each token (S, k), how many positions each query attends (S,))."""
    s, _ = x.shape
    qw, kw = h * hd, hkv * hd
    a = _rmsnorm(x, blk["ln1"], eps)
    qkv = _matmul(a, blk["wqkv"], quant)
    q = _rmsnorm(qkv[:, :qw].reshape(s, h, hd), blk["q_norm"], eps)
    kk = _rmsnorm(qkv[:, qw:qw + kw].reshape(s, hkv, hd), blk["k_norm"], eps)
    q, kk = _rope(q, theta), _rope(kk, theta)
    v = qkv[:, qw + kw:].reshape(s, hkv, hd)
    idx = _matmul(a, blk["widx"], quant)
    qi = _rope(idx[:, :hi * di].reshape(s, hi, di), theta)
    ki = _rope(idx[:, hi * di:hi * di + di].reshape(s, 1, di), theta)[:, 0]
    w = idx[:, hi * di + di:]
    qg = q.reshape(s, hkv, h // hkv, hd)

    def block(i):
        at = i * q_block
        qpos = at + jnp.arange(q_block)
        take = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=at, slice_size=q_block)
        sel = selection_mask(index_scores(take(qi), ki, take(w)), qpos, topk)
        sc = jnp.einsum("qgrd,sgd->grqs", take(qg), kk,
                        precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(sel[None, None], sc, -jnp.inf), axis=-1)
        return (jnp.einsum("grqs,sgd->qgrd", p, v,
                           precision=HIGHEST).reshape(q_block, qw),
                jnp.sum(sel, axis=1))

    att, n_sel = jax.lax.map(block, jnp.arange(s // q_block))
    x = x + _matmul(att.reshape(s, qw), blk["wo"], quant)
    # the expert layer in blocks of tokens: a 33 k-token request's
    # (token, expert) pairs at float32 would not fit beside the weights
    g = _rmsnorm(x, blk["ln2"], eps)
    mb = MOE_BLOCK if s % MOE_BLOCK == 0 else s
    y, e = jax.lax.map(lambda gb: moe(gb, blk, k, quant),
                       g.reshape(s // mb, mb, -1))
    return x + y.reshape(s, -1), e.reshape(s, k), n_sel.reshape(s)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, *, eps, quant):
    return _matmul(_rmsnorm(x, ln_f, eps), head, quant)


def forward_logits(params, cfg: dict, ids, *, quant=None, pad_to: int = 4096,
                   q_block: int = 128, rows=None, taps=None):
    """ids (S,) int -> logits (S, vocab) float32 (only positions `rows`,
    a slice, where given: the head over 33 k positions is 20 GB).  The
    sequence is padded on the right to a multiple of `pad_to` (causal
    attention and per-token experts keep padding out of the real
    positions).  `taps`, a dict, receives the reference's own routing
    and selection: "experts" (layers, S, k) and "attended" (layers, S),
    for the tests of the program's counts."""
    m = dims(cfg)
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    pad_to = max(q_block, min(pad_to, -(-s // q_block) * q_block))
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(F32)
    experts, attended = [], []
    for blk in params["blocks"]:
        x, e, n_sel = _layer(
            x, blk, h=m["h"], hkv=m["hkv"], hd=m["hd"], hi=m["hi"],
            di=m["di"], topk=m["topk"], k=m["k"], eps=m["eps"],
            theta=m["theta"], quant=quant, q_block=q_block)
        experts.append(e[:s])
        attended.append(n_sel[:s])
    if taps is not None:
        taps["experts"] = np.asarray(jnp.stack(experts))
        taps["attended"] = np.asarray(jnp.stack(attended))
    x = x[:s] if rows is None else x[:s][rows]
    return _head(x, params["ln_f"], params["head"], eps=m["eps"], quant=quant)


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
