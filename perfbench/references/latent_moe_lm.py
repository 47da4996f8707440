"""Plain reference of the decoder with latent attention, a dense layer in
front and then shared experts beside routed experts chosen inside groups
(``deepseek_v2``: deepseek-ai/DeepSeek-V2): float32 at
``precision="highest"``, the expanded form only (every key and value a
head made from the latent, no absorption), no cache, no batching, no code
of the program.  It also makes the seeded weights the program is handed.

The model, for ids of a sequence (hidden D, eps from the config, no
projection has a bias; each line the config's keys do not fix is listed
under ``assumed`` in the configuration's file):

- ``x0 = E[ids]``.  A layer, two RMSNorms: ``h = x + Attn(N1(x))``,
  ``y = h + MLP(N2(h))``.  After the last layer ``RMSNorm(x; ln_f)`` and
  the untied head.
- ``Attn(u)``: ``cq = RMSNorm(u Wqa)`` (``q_lora_rank``); ``q = cq Wqb``
  as H heads of ``qk_nope_head_dim + qk_rope_head_dim`` = (q_nope | q_pe).
  ``u Wkva`` (``kv_lora_rank + qk_rope_head_dim``) = (c | k_pe); ``c =
  RMSNorm(c)``, k_pe one key for all heads, not normed.  ``c Wkvb`` as H
  heads of ``qk_nope_head_dim + v_head_dim`` = (k_nope | v).  Rope on q_pe
  and k_pe: the pairs (2i, 2i + 1) turned by ``t f_i`` (`yarn_freqs`), cos
  and sin times ``m(factor, mscale) / m(factor, mscale_all_dim)``.  Scores
  ``(q_nope . k_nope + q_pe . k_pe) * (nope + rope)^(-1/2) * m(factor,
  mscale_all_dim)^2``, causal, softmax; the heads' sums of v side by side
  through ``Wo``.
- ``MLP`` of the first ``first_k_dense_replace`` layers:
  ``(silu(u W1) * (u W3)) W2`` of width ``intermediate_size``.  Of the
  others: ``s = softmax(u Wr)`` over all published experts; a group's
  score is the largest s among its experts; the ``topk_group`` groups of
  largest score stay (ties to the lower index) and the others' s count
  as 0; the ``num_experts_per_tok`` of largest s (ties to the lower
  index); ``w_e = routed_scaling_factor * s_e``, not renormalised
  (``norm_topk_prob`` false); ``MLP = Shared(u) + sum over the chosen e
  that are held here of w_e Expert_e(u)``, each expert a SwiGLU of
  ``moe_intermediate_size`` and Shared one SwiGLU of ``n_shared_experts``
  times that.  The held experts are ``n_routed_experts`` of the published
  ``expert_share.published`` from ``expert_share.first`` on: this chip's
  share of a layer that several chips divide; what the absent experts
  would have added is left out, and that partial result goes on to the
  next layer.

Where this departs from the model's own code, as recalled: the model code
de-interleaves the roped dims and turns them in half-split order, q and k
alike, which gives the same dot products as turning the pairs in place
(done here); it computes the router's softmax in float32 from a float32
copy of the weights (so does this, like everything else here).

Parameter layout (the hand-over format of this family's ``tensor_llm``
bundles): ``embed (V, D)``, ``blocks[i] = {ln1, ln2 (D), wqa (D, rq),
q_norm (rq), wqb (rq, H*(nope+rope)), wkva (D, rkv + rope), kv_norm (rkv),
wkvb (rkv, H*(nope+v)), wo (H*v, D)}`` and, a dense layer, ``wi (D, 2*F) =
[gate | up], wd (F, D)``; an expert layer, ``router (D, E), ewi (held, D,
2*f), ewd (held, f, D), swi (D, 2*fs), swd (fs, D)``; ``ln_f (D)``,
``head (D, V)``.

How it is computed, so that 17.4 k tokens x 128 heads fit beside 9.5 GB of
weights: layer by layer over the whole sequence, a layer's weights upcast
one matrix at a time, keys and values of the whole sequence expanded once
a layer (1.1 GB each at 17.4 k), the attention in blocks of ``q_block``
queries against every key behind the mask, the MLPs in blocks of
``MLP_BLOCK`` tokens, and the routed experts one held expert at a time on
every token of a block, combined by a weight that is 0 off a token's own
experts (no sort, no grouped product).
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


def dims(cfg: dict) -> dict:
    share = cfg["expert_share"]
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "rq": int(cfg["q_lora_rank"]), "rkv": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "v": int(cfg["v_head_dim"]),
            "f_dense": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["n_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "e": int(share["published"]), "first": int(share["first"]),
            "held": int(cfg["n_routed_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "groups": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
            "renorm": bool(cfg["norm_topk_prob"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "dense": int(cfg["first_k_dense_replace"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "yarn": _yarn(cfg.get("rope_scaling"))}


def _yarn(scaling) -> tuple:
    """(factor, original length, beta_fast, beta_slow, mscale,
    mscale_all_dim), or () for plain rope: a tuple, so that `dims` can
    be a static argument."""
    if not scaling:
        return ()
    return (float(scaling["factor"]),
            int(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"]),
            float(scaling["mscale"]), float(scaling["mscale_all_dim"]))


def param_count(cfg: dict) -> dict:
    """Matrix parameters by part: a layer's attention, its dense MLP, and
    of an expert layer what lies outside its routed experts (shared
    experts and router) and one routed expert; embedding and head."""
    m = dims(cfg)
    return {"attention": m["d"] * m["rq"] + m["rq"] * m["h"] * (
                m["nope"] + m["rope"]) + m["d"] * (m["rkv"] + m["rope"])
            + m["rkv"] * m["h"] * (m["nope"] + m["v"])
            + m["h"] * m["v"] * m["d"],
            "dense_mlp": 3 * m["d"] * m["f_dense"],
            "shared": 3 * m["d"] * m["fs"], "router": m["d"] * m["e"],
            "expert": 3 * m["d"] * m["f"],
            "outside": 2 * m["vocab"] * m["d"]}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device, already in the type they are served
    in: one jitted call a layer and one for what lies outside (a layer's
    held experts are 0.94 GB; their float32 draws do not pile up)."""
    m = dims(cfg)
    d, h = m["d"], m["h"]

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, F32, -lim, lim).astype(dtype)

    def attention(k):
        return {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
                "wqa": xavier(k[0], (d, m["rq"])),
                "q_norm": jnp.ones((m["rq"],), dtype),
                "wqb": xavier(k[1], (m["rq"], h * (m["nope"] + m["rope"]))),
                "wkva": xavier(k[2], (d, m["rkv"] + m["rope"])),
                "kv_norm": jnp.ones((m["rkv"],), dtype),
                "wkvb": xavier(k[3], (m["rkv"], h * (m["nope"] + m["v"]))),
                "wo": xavier(k[4], (h * m["v"], d))}

    @jax.jit
    def dense_layer(key):
        k = jax.random.split(key, 7)
        return dict(attention(k), wi=xavier(k[5], (d, 2 * m["f_dense"])),
                    wd=xavier(k[6], (m["f_dense"], d)))

    @jax.jit
    def expert_layer(key):
        k = jax.random.split(key, 10)
        return dict(
            attention(k), router=xavier(k[5], (d, m["e"])),
            ewi=xavier(k[6], (m["held"], d, 2 * m["f"])),
            ewd=xavier(k[7], (m["held"], m["f"], d)),
            swi=xavier(k[8], (d, 2 * m["fs"])),
            swd=xavier(k[9], (m["fs"], d)))

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


# -- YaRN -----------------------------------------------------------------------

def mscale(factor: float, a: float) -> float:
    """``m(s, a) = 0.1 a ln s + 1`` (1 for s <= 1)."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(rope: int, theta: float, yarn: tuple) -> np.ndarray:
    """``f_i`` of the pairs i = 0 .. rope / 2 - 1: ``e_i = theta^(-2i /
    rope)``; ``corr(r) = rope ln(L / (2 pi r)) / (2 ln theta)`` is the pair
    that makes r turns over the original length L; ``low =
    floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``, ``f_i = e_i (1 - ramp_i) +
    (e_i / factor) ramp_i``."""
    e = theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    if not yarn:
        return e
    factor, length, fast, slow, _, _ = yarn

    def corr(r):
        return rope * math.log(length / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), rope - 1)
    ramp = np.clip((np.arange(rope // 2) - low) / max(high - low, 1e-3), 0, 1)
    return e * (1 - ramp) + e / factor * ramp


def score_scale(m: dict) -> float:
    """What a score is multiplied by (0.114721 at the published values)."""
    s = (m["nope"] + m["rope"]) ** -0.5
    if m["yarn"]:
        s *= mscale(m["yarn"][0], m["yarn"][5]) ** 2
    return s


def _rope(x, pos, m: dict):
    """x (S, ..., rope) at positions pos (S,): each pair (2i, 2i + 1)
    turned in place by pos * f_i."""
    f = jnp.asarray(yarn_freqs(m["rope"], m["theta"], m["yarn"]), F32)
    gain = mscale(m["yarn"][0], m["yarn"][4]) \
        / mscale(m["yarn"][0], m["yarn"][5]) if m["yarn"] else 1.0
    ang = pos.astype(F32)[:, None] * f[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


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


def _swiglu(u, wi, wd, quant):
    f = wd.shape[0]
    gu = _matmul(u, wi, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], wd, quant)


def route(u, router, m: dict, quant=None):
    """u (S, D) -> (weights (S, k), experts (S, k) among all published):
    softmax scores; the `topk_group` groups whose best expert scores
    highest stay and the others' scores count as 0; the k largest;
    weighted by their scores times `scale`, renormalised first only where
    the config says so."""
    s = jax.nn.softmax(_matmul(u, router, quant), axis=-1)
    if m["groups"] > 1:
        per = m["e"] // m["groups"]
        best = jnp.max(s.reshape(-1, m["groups"], per), axis=-1)
        _, stay = jax.lax.top_k(best, m["topk_group"])
        kept = jnp.zeros_like(best, bool).at[
            jnp.arange(s.shape[0])[:, None], stay].set(True)
        s = jnp.where(jnp.repeat(kept, per, axis=1), s, 0.0)
    p, e = jax.lax.top_k(s, m["k"])
    if m["renorm"]:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20)
    return m["scale"] * p, e


def routed_part(u, blk, m: dict, quant=None):
    """What the experts held here (`ewi`, `ewd`: those from `first` on)
    add for tokens u (S, D): every held expert on every token, combined
    by a weight that is 0 off a token's own experts.  Returns (y (S, D),
    the experts of each token (S, k))."""
    held = blk["ewi"].shape[0]
    p, e = route(u, blk["router"], m, quant)
    gate = jnp.sum(p[:, :, None] * (
        e[:, :, None] == m["first"] + jnp.arange(held)), axis=1)  # (S, held)

    def one(i, y):
        return y + gate[:, i, None] * _swiglu(
            u, blk["ewi"][i], blk["ewd"][i], quant)

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u)), e


def shared_part(u, blk, quant=None):
    return _swiglu(u, blk["swi"], blk["swd"], quant)


class _Static(dict):
    """`dims` as a static argument of a jit: hashable by its items."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("m", "quant", "q_block"))
def _layer(x, blk, *, m, quant, q_block):
    """x (S, D), S a multiple of q_block.  Returns (x, the experts of
    each token (S, k), or (S, 0) for a dense layer)."""
    s, _ = x.shape
    h, nope, rope, v, eps = m["h"], m["nope"], m["rope"], m["v"], m["eps"]
    u = _rmsnorm(x, blk["ln1"], eps)
    spos = jnp.arange(s)
    kv = _matmul(u, blk["wkva"], quant)
    c = _rmsnorm(kv[:, :m["rkv"]], blk["kv_norm"], eps)
    k_pe = _rope(kv[:, m["rkv"]:], spos, m)                   # (S, rope)
    # the expanded form: every head's key and value of every position
    full = _matmul(c, blk["wkvb"], quant).reshape(s, h, nope + v)
    k_nope, val = full[..., :nope], full[..., nope:]
    scale = score_scale(m)

    def block(i):
        # a block of queries from its projection to its part of the
        # branch's output: nothing (S, H * v) wide is kept
        at = i * q_block
        qpos = at + jnp.arange(q_block)
        ub = jax.lax.dynamic_slice_in_dim(u, at, q_block)
        cq = _rmsnorm(_matmul(ub, blk["wqa"], quant), blk["q_norm"], eps)
        q = _matmul(cq, blk["wqb"], quant).reshape(q_block, h, nope + rope)
        q_pe = _rope(q[..., nope:], qpos, m)
        sc = (jnp.einsum("qhd,shd->hqs", q[..., :nope], k_nope,
                         precision=HIGHEST)
              + jnp.einsum("qhd,sd->hqs", q_pe, k_pe,
                           precision=HIGHEST)) * scale
        may = spos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(may[None], sc, -jnp.inf), axis=-1)
        att = jnp.einsum("hqs,shd->qhd", p, val,
                         precision=HIGHEST).reshape(q_block, h * v)
        return _matmul(att, blk["wo"], quant)

    att = jax.lax.map(block, jnp.arange(s // q_block)).reshape(s, -1)
    x = x + att
    g = _rmsnorm(x, blk["ln2"], eps)
    mb = MLP_BLOCK if s % MLP_BLOCK == 0 else s
    gb = g.reshape(s // mb, mb, -1)
    if "router" not in blk:
        y = jax.lax.map(lambda t: _swiglu(t, blk["wi"], blk["wd"], quant), gb)
        e = jnp.zeros((s, 0), jnp.int32)
    else:
        def moe(t):
            y, e = routed_part(t, blk, m, quant)
            return shared_part(t, blk, quant) + y, e

        y, e = jax.lax.map(moe, gb)
        e = e.reshape(s, m["k"])
    return x + y.reshape(s, -1), e


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, *, eps, quant):
    return _matmul(_rmsnorm(x, ln_f, eps), head, quant)


def forward_logits(params, cfg: dict, ids, *, quant=None, pad_to: int = 2048,
                   q_block: int = 0, rows=None, taps=None):
    """ids (S,) int -> logits (S, vocab) float32 (only positions `rows`,
    a slice, where given).  The sequence is padded on the right to a
    multiple of `pad_to` (causal attention and per-token MLPs keep padding
    out of the real positions).  `q_block` 0: 128 queries at a time, 32
    past 8 k tokens (a block's float32 scores are heads x block x S).
    `taps`, a dict, receives the reference's own routing: "experts"
    (expert layers, S, k), for the tests of the program's counts."""
    m = _Static(dims(cfg))
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    q_block = q_block or (128 if s <= 8192 else 32)
    pad_to = max(q_block, min(pad_to, -(-s // q_block) * q_block))
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(F32)
    experts = []
    for blk in params["blocks"]:
        x, e = _layer(x, blk, m=m, quant=quant, q_block=q_block)
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
