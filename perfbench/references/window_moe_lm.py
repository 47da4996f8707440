"""Plain reference of the decoder whose layers mix window and full
attention, a dense layer in front and then a shared expert beside routed
experts (``afmoe``: arcee-ai/Trinity-Large-Preview): float32 at
``precision="highest"``, no cache, no batching, no code of the program.  It
also makes the seeded weights the program is handed.

The model, for ids of a sequence (hidden D, eps from the config, no
projection has a bias; each line the config's keys do not fix is listed
under ``assumed`` in the configuration's file):

- ``x0 = E[ids] * sqrt(D)`` (``mup_enabled``).  A layer, with four
  RMSNorms: ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(MLP(N3(h)))``.  After
  the last layer ``RMSNorm(x; ln_f)`` and the untied head.
- ``Attn(u)``: ``q = u Wq`` as H heads of hd, ``k = u Wk`` and ``v = u Wv``
  as Hkv heads; per-head RMSNorm on q and k; rope (base ``rope_theta``,
  half-split, all hd dims) on ``sliding_attention`` layers only, none on
  ``full_attention`` layers; scores ``q . k / sqrt(hd)``, causal, and on a
  sliding layer key s is seen by query t iff ``t - window < s <= t``;
  ``Attn = (softmax(scores) v * sigmoid(u Wg)) Wo``.
- ``MLP`` of the first ``num_dense_layers`` layers:
  ``(silu(u W1) * (u W3)) W2`` of width ``intermediate_size``.  Of the
  others: ``s = sigmoid(u Wr)`` over all published experts; the
  ``num_experts_per_tok`` of largest ``s + b`` (ties to the lower index);
  ``w_e = route_scale * s_e / (sum of the chosen s + 1e-20)``;
  ``MLP = Shared(u) + sum over the chosen e that are held here of w_e
  Expert_e(u)``, each a SwiGLU of ``moe_intermediate_size``.  The held
  experts are ``num_experts`` of the published ``expert_share.published``
  from ``expert_share.first`` on: this chip's share of a layer that several
  chips divide; what the absent experts would have added is left out, and
  that partial result goes on to the next layer.

Parameter layout (the hand-over format of this family's ``tensor_llm``
bundles): ``embed (V, D)``, ``blocks[i] = {ln1, ln2, ln3, ln4 (D), wqkv (D,
H*hd + 2*Hkv*hd) = [q | k | v], q_norm (hd), k_norm (hd), wg (D, H*hd), wo
(H*hd, D)}`` and, a dense layer, ``wi (D, 2*F) = [gate | up], wd (F, D)``;
an expert layer, ``router (D, E), router_bias (E) float32, ewi (held, D,
2*f), ewd (held, f, D), swi (D, 2*fs), swd (fs, D)``; ``ln_f (D)``,
``head (D, V)``.

How it is computed, so that a 50 k-token request fits beside 10.8 GB of
weights: layer by layer over the whole sequence, a layer's weights upcast
one matrix at a time, the attention in blocks of ``q_block`` queries
against every key behind the mask, the MLPs in blocks of ``MLP_BLOCK``
tokens, and the routed experts one held expert at a time on every token
of a block, combined by a weight that is 0 off a token's own experts (no
sort, no grouped product: 226 MB of float32 weights at a time).
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
MLP_BLOCK = 2048        # tokens an MLP takes at a time
SLIDING, FULL = "sliding_attention", "full_attention"


def dims(cfg: dict) -> dict:
    share = cfg["expert_share"]
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
            "f_dense": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["num_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "e": int(share["published"]), "first": int(share["first"]),
            "held": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "scale": float(cfg["route_scale"]),
            "window": int(cfg["sliding_window"]),
            "kinds": tuple(cfg["layer_types"]),
            "dense": int(cfg["num_dense_layers"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "emb_scale": math.sqrt(float(cfg["hidden_size"]))
            if cfg.get("mup_enabled") else 1.0}


def param_count(cfg: dict) -> dict:
    """Matrix parameters by part: a layer's attention, its dense MLP, and
    of an expert layer what lies outside its routed experts (shared
    expert and router) and one routed expert; embedding and head."""
    m = dims(cfg)
    qw, kw = m["h"] * m["hd"], m["hkv"] * m["hd"]
    return {"attention": m["d"] * (2 * qw + 2 * kw) + qw * m["d"],
            "dense_mlp": 3 * m["d"] * m["f_dense"],
            "shared": 3 * m["d"] * m["fs"], "router": m["d"] * m["e"],
            "expert": 3 * m["d"] * m["f"],
            "outside": 2 * m["vocab"] * m["d"]}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device, already in the type they are served
    in: one jitted call a layer and one for what lies outside (a layer's
    held experts are 1.8 GB; their float32 draws do not pile up)."""
    m = dims(cfg)
    d, qw, kw = m["d"], m["h"] * m["hd"], m["hkv"] * m["hd"]

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, F32, -lim, lim).astype(dtype)

    def attention(k):
        out = {f"ln{i}": jnp.ones((d,), dtype) for i in (1, 2, 3, 4)}
        out.update(wqkv=xavier(k[0], (d, qw + 2 * kw)),
                   q_norm=jnp.ones((m["hd"],), dtype),
                   k_norm=jnp.ones((m["hd"],), dtype),
                   wg=xavier(k[1], (d, qw)), wo=xavier(k[2], (qw, d)))
        return out

    @jax.jit
    def dense_layer(key):
        k = jax.random.split(key, 5)
        return dict(attention(k), wi=xavier(k[3], (d, 2 * m["f_dense"])),
                    wd=xavier(k[4], (m["f_dense"], d)))

    @jax.jit
    def expert_layer(key):
        k = jax.random.split(key, 9)
        return dict(
            attention(k), router=xavier(k[3], (d, m["e"])),
            # small beside the spacing of the largest scores, so that it
            # decides some choices and not most
            router_bias=jax.random.uniform(k[4], (m["e"],), F32, -0.02, 0.02),
            ewi=xavier(k[5], (m["held"], d, 2 * m["f"])),
            ewd=xavier(k[6], (m["held"], m["f"], d)),
            swi=xavier(k[7], (d, 2 * m["fs"])),
            swd=xavier(k[8], (m["fs"], d)))

    @jax.jit
    def outside(key):
        k = jax.random.split(key, 2)
        return {"embed": xavier(k[0], (m["vocab"], d)),
                "ln_f": jnp.ones((d,), dtype),
                "head": xavier(k[1], (d, m["vocab"]))}

    keys = jax.random.split(key_from_seed(seed), m["layers"] + 1)
    out = outside(keys[-1])
    out["blocks"] = [(dense_layer if i < m["dense"] else expert_layer)(
        keys[i]) for i in range(m["layers"])]
    return out


# -- the forward pass ----------------------------------------------------------

def _matmul(x, w, quant):
    """x @ w in float32 at `highest`; under a control's lower precision,
    activations by row and weights by output column in that format."""
    w = w.astype(F32)
    if quant is not None:
        x, w = lowprec.fake(x, -1, quant), lowprec.fake(w, -2, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * w.astype(F32)


def _rope(x, pos, theta):
    """x (S, H, hd) at positions pos (S,): rotate halves, base `theta`."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(u, wi, wd, quant):
    f = wd.shape[0]
    gu = _matmul(u, wi, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], wd, quant)


def route(u, blk, k: int, scale: float, quant=None):
    """u (S, D) -> (weights (S, k), experts (S, k) among all published):
    sigmoid scores, the k of largest score + bias, weighted by their
    scores alone, renormalised and multiplied by `scale`."""
    s = jax.nn.sigmoid(_matmul(u, blk["router"], quant))
    _, e = jax.lax.top_k(s + blk["router_bias"].astype(F32), k)
    p = jnp.take_along_axis(s, e, axis=-1)
    return scale * p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20), e


def routed_part(u, blk, *, k, scale, first, quant=None):
    """What the experts held here (`ewi`, `ewd`: those from `first` on)
    add for tokens u (S, D): every held expert on every token, combined
    by a weight that is 0 off a token's own experts.  Returns (y (S, D),
    the experts of each token (S, k))."""
    held = blk["ewi"].shape[0]
    p, e = route(u, blk, k, scale, quant)
    gate = jnp.sum(p[:, :, None] * (
        e[:, :, None] == first + jnp.arange(held)), axis=1)    # (S, held)

    def one(i, y):
        return y + gate[:, i, None] * _swiglu(
            u, blk["ewi"][i], blk["ewd"][i], quant)

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u)), e


def shared_part(u, blk, quant=None):
    return _swiglu(u, blk["swi"], blk["swd"], quant)


@functools.partial(jax.jit, static_argnames=(
    "h", "hkv", "hd", "window", "roped", "k", "scale", "first", "eps",
    "theta", "quant", "q_block"))
def _layer(x, blk, *, h, hkv, hd, window, roped, k, scale, first, eps,
           theta, quant, q_block):
    """x (S, D), S a multiple of q_block.  `window` 0: a full layer.
    Returns (x, the experts of each token (S, k), or (S, 0) for a dense
    layer)."""
    s, _ = x.shape
    qw, kw = h * hd, hkv * hd
    u = _rmsnorm(x, blk["ln1"], eps)
    spos = jnp.arange(s)
    kv = _matmul(u, blk["wqkv"][:, qw:], quant)
    kk = _rmsnorm(kv[:, :kw].reshape(s, hkv, hd), blk["k_norm"], eps)
    if roped:
        kk = _rope(kk, spos, theta)
    v = kv[:, kw:].reshape(s, hkv, hd)

    def block(i):
        # a block of queries from its projection to its part of the
        # branch's output: nothing (S, H * hd) wide is kept
        at = i * q_block
        qpos = at + jnp.arange(q_block)
        ub = jax.lax.dynamic_slice_in_dim(u, at, q_block)
        q = _rmsnorm(_matmul(ub, blk["wqkv"][:, :qw], quant)
                     .reshape(q_block, h, hd), blk["q_norm"], eps)
        if roped:
            q = _rope(q, qpos, theta)
        may = spos[None, :] <= qpos[:, None]
        if window:
            may = may & (spos[None, :] > qpos[:, None] - window)
        sc = jnp.einsum("qgrd,sgd->grqs",
                        q.reshape(q_block, hkv, h // hkv, hd), kk,
                        precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(may[None, None], sc, -jnp.inf), axis=-1)
        att = jnp.einsum("grqs,sgd->qgrd", p, v,
                         precision=HIGHEST).reshape(q_block, qw)
        att = att * jax.nn.sigmoid(_matmul(ub, blk["wg"], quant))
        return _matmul(att, blk["wo"], quant)

    att = jax.lax.map(block, jnp.arange(s // q_block)).reshape(s, -1)
    x = x + _rmsnorm(att, blk["ln2"], eps)
    g = _rmsnorm(x, blk["ln3"], eps)
    mb = MLP_BLOCK if s % MLP_BLOCK == 0 else s
    gb = g.reshape(s // mb, mb, -1)
    if "router" not in blk:
        y = jax.lax.map(lambda t: _swiglu(t, blk["wi"], blk["wd"], quant), gb)
        e = jnp.zeros((s, 0), jnp.int32)
    else:
        def moe(t):
            y, e = routed_part(t, blk, k=k, scale=scale, first=first,
                               quant=quant)
            return shared_part(t, blk, quant) + y, e

        y, e = jax.lax.map(moe, gb)
        e = e.reshape(s, k)
    y = _rmsnorm(y.reshape(s, -1), blk["ln4"], eps)
    return x + y, e


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, *, eps, quant):
    return _matmul(_rmsnorm(x, ln_f, eps), head, quant)


def forward_logits(params, cfg: dict, ids, *, quant=None, pad_to: int = 2048,
                   q_block: int = 0, rows=None, taps=None):
    """ids (S,) int -> logits (S, vocab) float32 (only positions `rows`,
    a slice, where given: the head over 50 k positions is 40 GB).  The
    sequence is padded on the right to a multiple of `pad_to` (causal
    attention and per-token MLPs keep padding out of the real
    positions).  `q_block` 0: 128 queries at a time, 32 past 16 k tokens
    (a block's float32 scores are heads x block x S).  `taps`, a dict, receives the reference's own routing:
    "experts" (expert layers, S, k), for the tests of the program's
    counts."""
    m = dims(cfg)
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    q_block = q_block or (128 if s <= 16384 else 32)
    pad_to = max(q_block, min(pad_to, -(-s // q_block) * q_block))
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(F32) * m["emb_scale"]
    experts = []
    for kind, blk in zip(m["kinds"], params["blocks"]):
        x, e = _layer(
            x, blk, h=m["h"], hkv=m["hkv"], hd=m["hd"],
            window=m["window"] if kind == SLIDING else 0,
            roped=kind == SLIDING, k=m["k"], scale=m["scale"],
            first=m["first"], eps=m["eps"], theta=m["theta"], quant=quant,
            q_block=q_block)
        if e.shape[1]:
            experts.append(e[:s])
    if taps is not None:
        taps["experts"] = np.asarray(jnp.stack(experts))
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
