"""Plain reference of a pre-norm rotary SwiGLU decoder (the Ouro block, one
pass): float32 at ``precision="highest"``, no cache, no batching, no code of
the program.  It also makes the seeded weights the program is handed.

Parameter layout (the hand-over format of ``tensor_llm`` bundles):
``embed (V, D)``, ``blocks[i] = {ln1 (D), wqkv (D, D + 2*Hkv*hd), wo (D, D),
ln2 (D), wi (D, 2*F) = [gate | up], wd (F, D)}``, ``ln_f (D)``,
``head (D, V)``.

Departures from ``ByteDance/Ouro-2.6B`` as published are the configuration
file's ``reduced`` keys: one universal-transformer pass, rope base 10000.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import lowprec

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    hd = int(cfg["head_dim"])
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "hkv": int(cfg["num_key_value_heads"]), "hd": hd,
            "f": int(cfg["intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def param_count(cfg: dict) -> int:
    m = dims(cfg)
    kv = m["hkv"] * m["hd"]
    per_layer = (m["d"] * (m["d"] + 2 * kv) + m["d"] * m["d"]
                 + m["d"] * 2 * m["f"] + m["f"] * m["d"] + 2 * m["d"])
    return m["layers"] * per_layer + 2 * m["vocab"] * m["d"] + m["d"]


def key_from_seed(seed: int):
    """A PRNG key from a seed of more than 32 bits."""
    words = np.array([(int(seed) >> 32) & 0xFFFFFFFF,
                      int(seed) & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights on the device in ONE jitted call, already in the
    type they are served in (no host copy, no per-leaf dispatch)."""
    m = dims(cfg)
    kv = m["hkv"] * m["hd"]

    def xavier(key, shape):
        lim = math.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, shape, jnp.float32, -lim,
                                  lim).astype(dtype)

    def build(key):
        keys = jax.random.split(key, m["layers"] * 4 + 2)
        blocks = []
        for i in range(m["layers"]):
            k0, k1, k2, k3 = keys[4 * i:4 * i + 4]
            blocks.append({
                "ln1": jnp.ones((m["d"],), dtype),
                "wqkv": xavier(k0, (m["d"], m["d"] + 2 * kv)),
                "wo": xavier(k1, (m["d"], m["d"])),
                "ln2": jnp.ones((m["d"],), dtype),
                "wi": xavier(k2, (m["d"], 2 * m["f"])),
                "wd": xavier(k3, (m["f"], m["d"])),
            })
        return {"embed": xavier(keys[-2], (m["vocab"], m["d"])),
                "blocks": blocks, "ln_f": jnp.ones((m["d"],), dtype),
                "head": xavier(keys[-1], (m["d"], m["vocab"]))}

    return jax.jit(build)(key_from_seed(seed))


# -- the forward pass ----------------------------------------------------------

def _matmul(x, w, quant):
    w = w.astype(jnp.float32)
    if quant is not None:
        # weights per output column, activations per row
        x = lowprec.fake(x, -1, quant)
        w = lowprec.fake(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * w.astype(jnp.float32)


def _rope(x, theta):
    """x (S, H, hd): rotate halves by position, base `theta`."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("h", "hkv", "hd", "eps",
                                             "theta", "quant"))
def _layer(x, blk, *, h, hkv, hd, eps, theta, quant):
    s, d = x.shape
    kv = hkv * hd
    a = _rmsnorm(x, blk["ln1"], eps)
    qkv = _matmul(a, blk["wqkv"], quant)
    q = _rope(qkv[:, :d].reshape(s, h, hd), theta)
    k = _rope(qkv[:, d:d + kv].reshape(s, hkv, hd), theta)
    v = qkv[:, d + kv:].reshape(s, hkv, hd)
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, d)
    x = x + _matmul(att, blk["wo"], quant)
    a = _rmsnorm(x, blk["ln2"], eps)
    gu = _matmul(a, blk["wi"], quant)
    f = gu.shape[-1] // 2
    x = x + _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], blk["wd"], quant)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, *, eps, quant):
    return _matmul(_rmsnorm(x, ln_f, eps), head, quant)


def forward_logits(params, cfg: dict, ids, *, quant=None, pad_to: int = 128):
    """ids (S,) int -> logits (S, vocab) float32, layer by layer.  The
    sequence is padded on the right to a multiple of `pad_to` (causal
    attention keeps padding out of the real positions)."""
    m = dims(cfg)
    ids = np.asarray(ids, np.int32).reshape(-1)
    s = ids.shape[0]
    s_pad = -(-s // pad_to) * pad_to
    padded = np.zeros((s_pad,), np.int32)
    padded[:s] = ids
    x = params["embed"][padded].astype(jnp.float32)
    for blk in params["blocks"]:
        x = _layer(x, blk, h=m["h"], hkv=m["hkv"], hd=m["hd"], eps=m["eps"],
                   theta=m["theta"], quant=quant)
    return _head(x, params["ln_f"], params["head"], eps=m["eps"],
                 quant=quant)[:s]


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
    ref = forward_logits(params, cfg, ids)[p - 1:p - 1 + n]
    best = jnp.max(ref, axis=-1)

    def below_best(tokens):
        return np.asarray(best - jnp.take_along_axis(
            ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0])

    low = {}
    for quant in quants:
        logits = forward_logits(params, cfg, ids, quant=quant)
        low[quant] = below_best(jnp.argmax(logits[p - 1:p - 1 + n], axis=-1))
    return below_best(served), low
