"""Plain reference of the decoder whose layers are gated delta-rule linear
attention (KDA) three to one latent attention without rope, a dense layer
in front and then a shared expert beside routed experts (``kimi_linear``:
moonshotai/Kimi-Linear-48B-A3B-Instruct): float32 at
``precision="highest"``, KDA in its token-by-token recurrence (a
``lax.scan`` over tokens: the published definition), the convolution as a
plain padded sum, the latent attention expanded (every key and value a head
made from the latent), no cache, no batching, no kernels, no code of the
program.  It also makes the seeded weights the program is handed.

The model, for ids of a sequence (hidden D, eps from the config; each line
the config's keys do not fix is listed under ``assumed`` in the
configuration's file):

- ``x0 = E[ids]``.  A layer, two RMSNorms: ``h = x + Mix(N1(x))``,
  ``y = h + MLP(N2(h))``.  After the last layer ``RMSNorm(x; ln_f)`` and
  the untied head.  Published layer l (1-based) is KDA for l in
  ``linear_attn_config.kda_layers`` and latent for l in
  ``full_attn_layers``.
- ``KDA(u)`` (H = ``linear_attn_config.num_heads`` heads of d =
  ``head_dim``): ``q, k, v = SiLU(conv(u Wq)), SiLU(conv(u Wk)),
  SiLU(conv(u Wv))``, ``conv`` depthwise and causal over
  ``short_conv_kernel_size`` = K tokens, ``y_t = sum_i w[i] x_(t-K+1+i)``
  with zeros before the first token; a head's q and k L2-normed
  (``x / sqrt(sum x^2 + 1e-6)``), q times ``d^-1/2``; ``g_t = -exp(a_log_h)
  softplus((u Wfa) Wfb + dt_bias)`` a channel, ``a_t = exp(g_t)``; ``beta_t
  = sigmoid(u Wb)`` a head; the state S (d x d a head) from zero: ``S' =
  Diag(a_t) S``, ``w = beta_t (v_t - S'^T k_t)``, ``S = S' + k_t w^T``,
  ``o_t = S^T q_t``; ``y = Wo (RMSNorm(o_t; o_norm) * sigmoid((u Wga)
  Wgb))``, the norm a head with one weight of d.
- ``Latent(u)``: ``q = u Wq`` as heads of ``qk_nope_head_dim +
  qk_rope_head_dim`` (no rank, no norm); ``u Wkva`` = (c | k_r), ``c =
  RMSNorm(c)``, k_r one key for all heads; ``c Wkvb`` as heads of (k_nope |
  v); nothing is turned (``mla_use_nope``); scores ``(q_nope . k_nope + q_r
  . k_r) (nope + rope)^-1/2``, causal, softmax; the heads' sums of v through
  ``Wo``.
- ``MLP`` of the first ``first_k_dense_replace`` layers: ``(silu(u W1) *
  (u W3)) W2`` of ``intermediate_size``.  Of the others: ``s = sigmoid(u
  Wr)`` over all published experts; the ``num_experts_per_token`` of
  largest ``s + bias`` (ties to the lower index); ``w_e =
  routed_scaling_factor s_e / (sum of the chosen s + 1e-20)``; ``MLP =
  Shared(u) + sum over the chosen e held here of w_e Expert_e(u)``, each a
  SwiGLU of ``moe_intermediate_size``, Shared one of ``num_shared_experts``
  times that.  The held experts are ``num_experts`` of the published
  ``expert_share.published`` from ``expert_share.first`` on: this chip's
  share of a layer that several chips divide; what the absent experts
  would have added is left out, and that partial result goes on.

Parameter layout (the hand-over format of this family's ``tensor_llm``
bundles): ``embed (V, D)``; a KDA layer ``{ln1, ln2 (D), wqkv (D, 3 H d) =
[q | k | v], conv (K, 3 H d), wfa (D, r), wfb (r, H d), dt_bias (H d),
a_log (H), wb (D, H), wga (D, r), wgb (r, H d), o_norm (d), wo (H d, D)}``,
r = d; a latent layer ``{ln1, ln2, wq (D, H (nope + rope)), wkva (D, rkv +
rope), kv_norm (rkv), wkvb (rkv, H (nope + v)), wo (H v, D)}``; and, a dense
layer, ``wi (D, 2 F) = [gate | up], wd (F, D)``; an expert layer, ``router
(D, E), router_bias (E) float32, ewi (held, D, 2 f), ewd (held, f, D), swi
(D, 2 fs), swd (fs, D)``; ``ln_f (D)``, ``head (D, V)``.

How it is computed, so that 17.4 k tokens fit the chip beside 8.7 GB of
weights: layer by layer over the whole sequence, a layer's weights upcast
one matrix at a time; a KDA layer's q, k, v, g and gate whole (0.3 GB each
at 18.4 k) and one scan over the tokens; a latent layer's keys and values
expanded once, the attention in blocks of ``q_block`` queries; the MLPs in
blocks of ``MLP_BLOCK`` tokens, the routed experts one held expert at a
time on every token of a block, combined by a weight that is 0 off a
token's own experts.
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
KDA, LATENT = "kda", "latent"
L2_EPS = 1e-6


def dims(cfg: dict) -> dict:
    share, lin = cfg["expert_share"], cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    kinds = tuple(KDA if i in kda else LATENT for i in range(1, layers + 1))
    if kda & full or (kda | full) != set(range(1, layers + 1)):
        raise ValueError("linear_attn_config names each layer once, 1-based")
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "kh": int(lin["num_heads"]), "kd": int(lin["head_dim"]),
            "conv": int(lin["short_conv_kernel_size"]),
            # the rank of the decay's and the gate's pair: the KDA head's
            # size in the model's code (assumed)
            "r": int(lin["head_dim"]),
            "rkv": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "v": int(cfg["v_head_dim"]),
            "f_dense": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["num_shared_experts"])
            * int(cfg["moe_intermediate_size"]),
            "e": int(share["published"]), "first": int(share["first"]),
            "held": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_token"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "dense": int(cfg["first_k_dense_replace"]),
            "layers": layers, "kinds": kinds,
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"])}


def param_count(cfg: dict) -> dict:
    """Matrix parameters by part: a KDA layer's mixer (the convolutions'
    weights among them), a latent layer's, a dense MLP, and of an expert
    layer what lies outside its routed experts (shared expert and router)
    and one routed expert; embedding and head."""
    m = dims(cfg)
    hd = m["kh"] * m["kd"]
    return {"kda": 3 * m["d"] * hd + hd * m["d"]
            + 2 * (m["d"] * m["r"] + m["r"] * hd) + m["d"] * m["kh"]
            + 3 * hd * m["conv"],
            "latent": m["d"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["rkv"] + m["rope"])
            + m["rkv"] * m["h"] * (m["nope"] + m["v"])
            + m["h"] * m["v"] * m["d"],
            "dense_mlp": 3 * m["d"] * m["f_dense"],
            "shared": 3 * m["d"] * m["fs"], "router": m["d"] * m["e"],
            "expert": 3 * m["d"] * m["f"],
            "outside": 2 * m["vocab"] * m["d"]}


def total_params(cfg: dict) -> int:
    """The matrix parameters this chip holds."""
    m, n = dims(cfg), param_count(cfg)
    experts = m["layers"] - m["dense"]
    return (m["kinds"].count(KDA) * n["kda"]
            + m["kinds"].count(LATENT) * n["latent"]
            + m["dense"] * n["dense_mlp"] + n["outside"]
            + experts * (n["shared"] + n["router"] + m["held"] * n["expert"]))


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device, already in the type they are served
    in: one jitted call a layer and one for what lies outside (a layer's
    held experts are 0.45 GB; their float32 draws do not pile up)."""
    m = dims(cfg)
    d, hd, r = m["d"], m["kh"] * m["kd"], m["r"]

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, F32, -lim, lim).astype(dtype)

    def kda(k):
        # the decay's two vectors as the model's code draws them: exp(a_log)
        # in [1, 16), softplus(dt_bias) a step in [0.001, 0.1)
        dt = jnp.exp(jax.random.uniform(k[8], (hd,), F32, math.log(1e-3),
                                        math.log(0.1)))
        return {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
                "wqkv": xavier(k[0], (d, 3 * hd)),
                # a depthwise convolution's usual draw: taps within K^-1/2
                "conv": jax.random.uniform(
                    k[1], (m["conv"], 3 * hd), F32, -m["conv"] ** -0.5,
                    m["conv"] ** -0.5).astype(dtype),
                "wfa": xavier(k[2], (d, r)), "wfb": xavier(k[3], (r, hd)),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "a_log": jnp.log(jax.random.uniform(
                    k[9], (m["kh"],), F32, 1.0, 16.0)).astype(dtype),
                "wb": xavier(k[4], (d, m["kh"])),
                "wga": xavier(k[5], (d, r)), "wgb": xavier(k[6], (r, hd)),
                "o_norm": jnp.ones((m["kd"],), dtype),
                "wo": xavier(k[7], (hd, d))}

    def latent(k):
        h = m["h"]
        return {"ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
                "wq": xavier(k[0], (d, h * (m["nope"] + m["rope"]))),
                "wkva": xavier(k[1], (d, m["rkv"] + m["rope"])),
                "kv_norm": jnp.ones((m["rkv"],), dtype),
                "wkvb": xavier(k[2], (m["rkv"], h * (m["nope"] + m["v"]))),
                "wo": xavier(k[3], (h * m["v"], d))}

    @functools.partial(jax.jit, static_argnames=("kind", "dense"))
    def layer(key, *, kind, dense):
        k = jax.random.split(key, 16)
        out = kda(k) if kind == KDA else latent(k)
        if dense:
            return dict(out, wi=xavier(k[10], (d, 2 * m["f_dense"])),
                        wd=xavier(k[11], (m["f_dense"], d)))
        return dict(
            out, router=xavier(k[10], (d, m["e"])),
            # small beside the spacing of the largest scores, so that it
            # decides some choices and not most
            router_bias=jax.random.uniform(k[11], (m["e"],), F32, -0.02,
                                           0.02),
            ewi=xavier(k[12], (m["held"], d, 2 * m["f"])),
            ewd=xavier(k[13], (m["held"], m["f"], d)),
            swi=xavier(k[14], (d, 2 * m["fs"])),
            swd=xavier(k[15], (m["fs"], d)))

    @jax.jit
    def outside(key):
        k = jax.random.split(key, 2)
        return {"embed": xavier(k[0], (m["vocab"], d)),
                "ln_f": jnp.ones((d,), dtype),
                "head": xavier(k[1], (d, m["vocab"]))}

    keys = jax.random.split(key_from_seed(seed), m["layers"] + 1)
    out = outside(keys[-1])
    out["blocks"] = [layer(keys[i], kind=kind, dense=i < m["dense"])
                     for i, kind in enumerate(m["kinds"])]
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


def _swiglu(u, wi, wd, quant):
    f = wd.shape[0]
    gu = _matmul(u, wi, quant)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], wd, quant)


def route(u, blk, m: dict, quant=None):
    """u (S, D) -> (weights (S, k), experts (S, k) among all published):
    sigmoid scores, the k of largest score + bias (ties to the lower
    index), weighted by their scores alone, renormalised, times `scale`."""
    s = jax.nn.sigmoid(_matmul(u, blk["router"], quant))
    _, e = jax.lax.top_k(s + blk["router_bias"].astype(F32), m["k"])
    p = jnp.take_along_axis(s, e, axis=-1)
    return m["scale"] * p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20), e


def routed_part(u, blk, m: dict, quant=None):
    """What the experts held here (`ewi`, `ewd`: those from `first` on)
    add for tokens u (S, D): every held expert on every token, combined
    by a weight that is 0 off a token's own experts.  Returns (y (S, D),
    the experts of each token (S, k))."""
    held = blk["ewi"].shape[0]
    p, e = route(u, blk, m, quant)
    gate = jnp.sum(p[:, :, None] * (
        e[:, :, None] == m["first"] + jnp.arange(held)), axis=1)  # (S, held)

    def one(i, y):
        return y + gate[:, i, None] * _swiglu(
            u, blk["ewi"][i], blk["ewd"][i], quant)

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u)), e


def shared_part(u, blk, quant=None):
    return _swiglu(u, blk["swi"], blk["swd"], quant)


def conv_silu(x, w):
    """The causal depthwise convolution as a plain padded sum, then SiLU:
    x (S, W), w (K, W); ``y_t = sum_i w[i] x_(t - K + 1 + i)``, zeros
    before the first token."""
    k, s = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + s] * w[i].astype(F32)
                           for i in range(k)))


def kda_recurrence(q, k, v, g, beta):
    """The delta rule token by token, from a zero state: q, k, g (S, H,
    d), v (S, H, d), beta (S, H).  Returns o (S, H, d)."""
    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, :, None] * state          # Diag(a_t) S
        w = bt[:, None] * (vt - jnp.sum(kt[:, :, None] * state, axis=1))
        state = state + kt[:, :, None] * w[:, None, :]
        return state, jnp.sum(qt[:, :, None] * state, axis=1)

    h, d = q.shape[1], q.shape[2]
    _, o = jax.lax.scan(token, jnp.zeros((h, d, v.shape[2]), F32),
                        (q, k, v, g, beta))
    return o


class _Static(dict):
    """`dims` as a static argument of a jit: hashable by its items."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _kda_mix(u, blk, m, quant):
    """u (S, D) normed -> the mixer's output (S, D)."""
    s = u.shape[0]
    h, d = m["kh"], m["kd"]
    hd = h * d

    def conved(i):
        x = _matmul(u, blk["wqkv"][:, i * hd:(i + 1) * hd], quant)
        return conv_silu(x, blk["conv"][:, i * hd:(i + 1) * hd]) \
            .reshape(s, h, d)

    q, k, v = _l2(conved(0)) * d ** -0.5, _l2(conved(1)), conved(2)
    a = _matmul(_matmul(u, blk["wfa"], quant), blk["wfb"], quant)
    g = -jnp.exp(blk["a_log"].astype(F32))[None, :, None] * jax.nn.softplus(
        a.reshape(s, h, d) + blk["dt_bias"].astype(F32).reshape(h, d))
    beta = jax.nn.sigmoid(_matmul(u, blk["wb"], quant))
    o = kda_recurrence(q, k, v, g, beta)
    gate = _matmul(_matmul(u, blk["wga"], quant), blk["wgb"], quant)
    o = _rmsnorm(o, blk["o_norm"], m["eps"]) \
        * jax.nn.sigmoid(gate.reshape(s, h, d))
    return _matmul(o.reshape(s, hd), blk["wo"], quant)


def _latent_mix(u, blk, m, quant, q_block):
    """u (S, D) normed, S a multiple of q_block -> the mixer's output."""
    s = u.shape[0]
    h, nope, rope, v = m["h"], m["nope"], m["rope"], m["v"]
    spos = jnp.arange(s)
    kv = _matmul(u, blk["wkva"], quant)
    c = _rmsnorm(kv[:, :m["rkv"]], blk["kv_norm"], m["eps"])
    k_r = kv[:, m["rkv"]:]                                    # (S, rope)
    # the expanded form: every head's key and value of every position
    full = _matmul(c, blk["wkvb"], quant).reshape(s, h, nope + v)
    k_nope, val = full[..., :nope], full[..., nope:]
    scale = (nope + rope) ** -0.5

    def block(i):
        at = i * q_block
        qpos = at + jnp.arange(q_block)
        ub = jax.lax.dynamic_slice_in_dim(u, at, q_block)
        q = _matmul(ub, blk["wq"], quant).reshape(q_block, h, nope + rope)
        sc = (jnp.einsum("qhd,shd->hqs", q[..., :nope], k_nope,
                         precision=HIGHEST)
              + jnp.einsum("qhd,sd->hqs", q[..., nope:], k_r,
                           precision=HIGHEST)) * scale
        may = spos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(may[None], sc, -jnp.inf), axis=-1)
        att = jnp.einsum("hqs,shd->qhd", p, val,
                         precision=HIGHEST).reshape(q_block, h * v)
        return _matmul(att, blk["wo"], quant)

    return jax.lax.map(block, jnp.arange(s // q_block)).reshape(s, -1)


@functools.partial(jax.jit, static_argnames=("m", "kind", "quant",
                                             "q_block"))
def _layer(x, blk, *, m, kind, quant, q_block):
    """x (S, D), S a multiple of q_block.  Returns (x, the experts of
    each token (S, k), or (S, 0) for a dense layer)."""
    s = x.shape[0]
    u = _rmsnorm(x, blk["ln1"], m["eps"])
    x = x + (_kda_mix(u, blk, m, quant) if kind == KDA
             else _latent_mix(u, blk, m, quant, q_block))
    g = _rmsnorm(x, blk["ln2"], m["eps"])
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
    multiple of `pad_to` (every layer is causal and the MLPs are a token
    each, so padding stays out of the real positions).  `q_block` 0: 128
    queries of a latent layer at a time.  `taps`, a dict, receives the
    reference's own routing: "experts" (expert layers, S, k), for the
    tests of the program's counts."""
    m = _Static(dims(cfg))
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    q_block = q_block or 128
    pad_to = max(q_block, min(pad_to, -(-s // q_block) * q_block))
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(F32)
    experts = []
    for kind, blk in zip(m["kinds"], params["blocks"]):
        x, e = _layer(x, blk, m=m, kind=kind, quant=quant, q_block=q_block)
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
