"""Decoder-only transformer (zoo://transformer) — the long-context family.

No reference counterpart (the reference is CNN-era inference plumbing;
SURVEY.md §5.7 maps its closest analogs). This is the model family that
exercises the framework's long-context machinery end-to-end:

- **Streaming decode**: the KV cache is explicit state tensors, so
  autoregressive generation runs as a *pipeline loop* — cache loops
  through tensor_repo exactly like the LSTM's (h, c), one token per
  frame (tests/test_streaming_models.py pattern).
- **Sequence parallelism**: full-sequence forward (prefill/training)
  attends via parallel/ring_attention.py when a mesh is given — the
  sequence dim shards over `sp` and K/V blocks rotate over ICI.

Architecture: pre-RMSNorm, rotary position embeddings, multi-head
causal attention, SwiGLU MLP — the standard modern decoder block, all
MXU-shaped matmuls in the caller's dtype (bf16 on TPU).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import layers as L
from nnstreamer_tpu.models.zoo import register_model


def rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


def rope(x, pos, base=10000.0):
    """Rotary embedding. x: (B, S, H, D); pos: (S,) absolute positions;
    `base` travels with the model (10000.0 is the dense family's)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]   # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def init_params(key=None, *, d_model=64, n_heads=4, n_layers=2, d_ff=None,
                vocab=256, n_kv_heads=None, seed=0) -> Dict[str, Any]:
    """n_kv_heads < n_heads = grouped-query attention: the KV cache (and
    K/V projections) shrink by the group factor — the standard long-
    context memory lever. Default (None) = full multi-head."""
    if key is None:
        key = jax.random.PRNGKey(seed)
    d_ff = d_ff or 4 * d_model
    n_kv = n_kv_heads or n_heads
    if n_heads % n_kv:
        raise ValueError(f"n_heads={n_heads} not divisible by "
                         f"n_kv_heads={n_kv}")
    kv_dim = (d_model // n_heads) * n_kv
    keys = jax.random.split(key, n_layers * 4 + 2)
    blocks = []
    for i in range(n_layers):
        k0, k1, k2, k3 = keys[4 * i:4 * i + 4]
        blocks.append({
            "ln1": jnp.ones((d_model,), jnp.float32),
            "wqkv": L.xavier_init(k0, (d_model, d_model + 2 * kv_dim)),
            "wo": L.xavier_init(k1, (d_model, d_model)),
            "ln2": jnp.ones((d_model,), jnp.float32),
            "wi": L.xavier_init(k2, (d_model, 2 * d_ff)),   # SwiGLU gate+up
            "wd": L.xavier_init(k3, (d_ff, d_model)),
        })
    return {
        "embed": L.xavier_init(keys[-2], (vocab, d_model)),
        "blocks": blocks,
        "ln_f": jnp.ones((d_model,), jnp.float32),
        "head": L.xavier_init(keys[-1], (d_model, vocab)),
    }


def _mlp(blk, x, dtype):
    gate_up = x @ blk["wi"].astype(dtype)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ blk["wd"].astype(dtype)


def _qkv(blk, x, n_heads, dtype):
    """Project to q (n_heads) and k/v (n_kv_heads, inferred from the
    weight shape), then repeat KV groups so attention sees full heads —
    the cache stays narrow, the compute path stays uniform."""
    b, s, d = x.shape
    hd = d // n_heads
    total = blk["wqkv"].shape[1]
    kv_dim = (total - d) // 2
    n_kv = kv_dim // hd
    qkv = x @ blk["wqkv"].astype(dtype)
    q = qkv[..., :d].reshape(b, s, n_heads, hd)
    k = qkv[..., d:d + kv_dim].reshape(b, s, n_kv, hd)
    v = qkv[..., d + kv_dim:].reshape(b, s, n_kv, hd)
    return q, k, v


def expand_kv(k, n_heads):
    """(B, S, n_kv, D) → (B, S, n_heads, D) by group repetition."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def apply_seq(params, ids, *, n_heads=4, dtype=jnp.float32,
              mesh=None, sp_axis: str = "sp", attn: str = "auto"):
    """Full-sequence forward: (B, S) int32 → (B, S, vocab) logits.

    With a mesh, attention runs ring-parallel over `sp_axis` (sequence
    sharded, K/V rotating over ICI). Without, `attn` picks the kernel:
    "pallas" = the flash-attention Pallas kernel (~7x over the XLA
    softmax at S=2048 on v5e, driver-measured in BENCH_r04.json —
    growing with S), "xla" = plain causal softmax,
    "auto" = pallas when the sequence divides its 128-blocks, else xla.
    """
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    b, s = ids.shape
    x = params["embed"][ids].astype(dtype)
    pos = jnp.arange(s)
    # explicit attn="pallas" always takes the kernel (flash_attention
    # raises its pad-upstream error on indivisible S rather than
    # silently substituting the XLA path); "auto" requires 128-blocks
    use_pallas = mesh is None and (
        attn == "pallas" or (attn == "auto" and s % 128 == 0))
    for blk in params["blocks"]:
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        q, k, v = _qkv(blk, h, n_heads, dtype)
        q, k = rope(q, pos), rope(k, pos)
        k, v = expand_kv(k, n_heads), expand_kv(v, n_heads)
        if mesh is not None:
            attn = ring_attention(q, k, v, mesh=mesh, axis=sp_axis,
                                  causal=True)
        elif use_pallas:
            from nnstreamer_tpu.backends.pallas_ops import flash_attention

            # per-path auto block sizes (512² resident / 1024² K-grid,
            # see _flash_plan): the MXU needs big blocks — 128² here
            # measured ~12× slower than the defaults at S=2048
            attn = flash_attention(q, k, v, causal=True)
        else:
            attn = reference_attention(q, k, v, causal=True)
        attn = attn.reshape(b, s, -1)
        x = x + attn @ blk["wo"].astype(dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + _mlp(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    return (x @ params["head"].astype(dtype)).astype(jnp.float32)


def apply_seq_kv(params, ids, *, n_heads=4, dtype=jnp.float32):
    """Full-sequence forward that ALSO returns every layer's rope'd K/V.

    (B, S) int32 → (logits (B, S, vocab) f32,
                    k (L, B, S, n_kv, D), v (L, B, S, n_kv, D))

    This is the prefill path of the continuous-batching LLM engine
    (llm/engine.py): one bucketed forward computes the prompt's whole KV
    set, which then lands in the paged cache, instead of `generate()`'s
    per-token `_step_jit` loop. The attention here is deliberately
    formulated EXACTLY like `_step_impl`'s cached attention — the same
    f32 einsums ("bqhd,bkhd->bhqk" / "bhqk,bkhd->bqhd"), the same -1e30
    additive mask, softmax in f32 — rather than reusing `apply_seq`'s
    kernel dispatch: masked positions then contribute exact 0.0 terms in
    both paths, so the paged engine's tokens match `generate()`
    token-for-token at temperature 0 (tests/test_llm.py parity gate).
    """
    b, s = ids.shape
    x = params["embed"][ids].astype(dtype)
    pos = jnp.arange(s)
    causal = (jnp.arange(s)[None, :] <=
              jnp.arange(s)[:, None])[None, None, :, :]   # (1,1,Sq,Sk)
    ks, vs = [], []
    for blk in params["blocks"]:
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        q, k, v = _qkv(blk, h, n_heads, dtype)
        q, k = rope(q, pos), rope(k, pos)
        ks.append(k)
        vs.append(v)
        hd = x.shape[-1] // n_heads
        kcx = expand_kv(k, n_heads).astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kcx) * hd ** -0.5
        sc = jnp.where(causal, sc, -1e30)
        pattn = jax.nn.softmax(sc, axis=-1)
        vcx = expand_kv(v, n_heads).astype(jnp.float32)
        attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vcx).astype(dtype)
        x = x + attn.reshape(b, s, -1) @ blk["wo"].astype(dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + _mlp(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = (x @ params["head"].astype(dtype)).astype(jnp.float32)
    return logits, jnp.stack(ks, axis=0), jnp.stack(vs, axis=0)


def init_cache(*, batch=1, max_len=128, d_model=64, n_heads=4, n_layers=2,
               n_kv_heads=None, dtype=jnp.float32):
    """KV cache as TWO stacked tensors (pipeline-friendly state):
    k/v: (L, B, max_len, n_kv, D) — GQA narrows it by the group factor.
    Position rides a (1,) int32 tensor.

    `dtype` is the cache STORAGE type; attention math upcasts to f32 on
    read regardless (softmax/accumulator precision unchanged). bf16
    storage halves the cache's HBM footprint and sweep traffic —
    measured round 5 (after the in-place write-through fix): 0.85 vs
    1.07 ms/step at d=1024/4L/B=8/max_len=2048, +26% tokens/s. (The
    earlier "~2×" held only while every step also COPIED the cache;
    the copy scaled with storage bytes and is gone.)"""
    hd = d_model // n_heads
    n_kv = n_kv_heads or n_heads
    shape = (n_layers, batch, max_len, n_kv, hd)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.zeros((1,), jnp.int32))


def _step_impl(params, ids, k_cache, v_cache, pos, n_heads, dtype, proj):
    """Shared decode-step body for the float and W8A8 paths.

    `proj(store, name, x)` runs one projection matmul and returns in
    `dtype` — the ONLY thing the two paths differ in (dense `x @ w`
    here; int8 `w8a8_matmul` in models/quant.py). Everything
    load-bearing lives once: the ring-slot write goes THROUGH the
    stacked cache (one dynamic_update_slice on the full (L,B,S,Hkv,D)
    array per tensor) — never unstack and restack: a per-layer
    k_cache[li] → update → jnp.stack round-trip defeats XLA's in-place
    aliasing of the donated cache inside lax.scan/_step_jit and copies
    the whole cache every token (measured 2.6× slower at max_len=2048:
    2.24 vs 0.86 ms/step, bit-identical outputs)."""
    b = ids.shape[0]
    max_len = k_cache.shape[2]
    p = pos.astype(jnp.int32)[0]
    slot = p % max_len
    x = params["embed"][ids[:, 0]][:, None, :].astype(dtype)   # (B,1,D)
    pvec = p[None]
    for li, blk in enumerate(params["blocks"]):
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        d = x.shape[-1]
        hd = d // n_heads
        qkv = proj(blk, "wqkv", h)
        kv_dim = (qkv.shape[-1] - d) // 2
        n_kv = kv_dim // hd
        q = qkv[..., :d].reshape(b, 1, n_heads, hd)
        k = qkv[..., d:d + kv_dim].reshape(b, 1, n_kv, hd)
        v = qkv[..., d + kv_dim:].reshape(b, 1, n_kv, hd)
        q, k = rope(q, pvec), rope(k, pvec)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype)[None], (li, 0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype)[None], (li, 0, slot, 0, 0))
        kc, vc = k_cache[li], v_cache[li]
        # attend over the populated window (all slots once wrapped)
        scale = hd ** -0.5
        # cache layout is (B, max_len, n_kv, D): expand KV groups to
        # full heads for the attention einsum; scores/softmax in f32
        # regardless of the cache storage dtype
        kcx = expand_kv(kc, n_heads).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kcx) * scale                 # (B,H,1,max_len)
        mask = (jnp.arange(max_len) <=
                jnp.minimum(p, max_len - 1))[None, None, None, :]
        s = jnp.where(mask, s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1)
        vcx = expand_kv(vc, n_heads).astype(jnp.float32)
        attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vcx).astype(dtype)
        x = x + proj(blk, "wo", attn.reshape(b, 1, -1))
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        gate, up = jnp.split(proj(blk, "wi", h), 2, axis=-1)
        x = x + proj(blk, "wd", jax.nn.silu(gate) * up)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = proj(params, "head", x[:, 0]).astype(jnp.float32)
    return (logits, k_cache, v_cache, (p + 1)[None].astype(jnp.int32))


def apply_step(params, ids, k_cache, v_cache, pos, *, n_heads=4,
               dtype=jnp.float32):
    """One streaming decode step: ids (B, 1) int32 + cache → logits
    (B, vocab) + updated cache. Static shapes throughout: the cache is a
    TRUE ring — writes land at pos % max_len, so past max_len tokens the
    window slides (sliding-window attention over the last max_len
    tokens; RoPE keys carry absolute positions, so relative geometry
    stays correct across the wrap). Body shared with the W8A8 twin via
    `_step_impl`."""
    def proj(store, name, x):
        return x @ store[name].astype(dtype)

    return _step_impl(params, ids, k_cache, v_cache, pos, n_heads,
                      dtype, proj)


#: one compiled decode step per (n_heads, dtype) — generate() calls
#: reuse it instead of paying a fresh XLA compile per invocation
_step_jit = jax.jit(apply_step, static_argnames=("n_heads", "dtype"),
                    donate_argnums=(2, 3))


def _decode_one(params, cur, k_cache, v_cache, pos, key, *, n_heads,
                dtype, temperature, top_k):
    """Step + sample fused in ONE program: a token in, the next token
    out. Keeps the decode loop at one dispatch per token — per-token
    host-side argmax/sort/categorical ops each cost a full dispatch
    round-trip on remote backends (measured 11 tok/s vs ~190 fused)."""
    logits, kc, vc, pos = apply_step(params, cur[:, None], k_cache,
                                     v_cache, pos, n_heads=n_heads,
                                     dtype=dtype)
    if temperature <= 0:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        lg = logits / temperature
        if top_k > 0:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -1e30, lg)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, lg, axis=-1).astype(jnp.int32)
    return nxt, kc, vc, pos, key


_decode_jit = jax.jit(
    _decode_one,
    static_argnames=("n_heads", "dtype", "temperature", "top_k"),
    donate_argnums=(2, 3))


def generate(params, prompt_ids, n_tokens, *, n_heads=4, max_len=128,
             temperature: float = 0.0, top_k: int = 0, seed: int = 0,
             dtype=jnp.float32):
    """Autoregressive sampling: prompt (B, P) int32 → (B, P + n_tokens).

    temperature=0 is greedy argmax; otherwise softmax sampling, optionally
    top-k truncated (clamped to the vocab). One jitted step with donated
    cache — the KV ring stays in HBM across tokens, and each sampled
    token's D2H overlaps the next step's compute."""
    import numpy as np

    d_model = params["embed"].shape[1]
    n_layers = len(params["blocks"])
    hd = d_model // n_heads
    n_kv = (params["blocks"][0]["wqkv"].shape[1] - d_model) // 2 // hd
    b, plen = prompt_ids.shape
    if plen == 0:
        raise ValueError("generate() needs a non-empty prompt (the model "
                         "has no BOS convention to start from)")
    vocab = params["head"].shape[1]
    top_k = min(top_k, vocab)
    kc, vc, pos = init_cache(batch=b, max_len=max_len, d_model=d_model,
                             n_heads=n_heads, n_layers=n_layers,
                             n_kv_heads=n_kv)

    key = jax.random.PRNGKey(seed)
    out = [np.asarray(prompt_ids)]
    # prefill all but the last prompt token (its step is fused into the
    # first decode call)
    for t in range(plen - 1):
        _, kc, vc, pos = _step_jit(params, prompt_ids[:, t:t + 1],
                                   kc, vc, pos, n_heads=n_heads,
                                   dtype=dtype)
    cur = prompt_ids[:, plen - 1]
    pending = []                                # device tokens, D2H deferred
    for _ in range(n_tokens):
        cur, kc, vc, pos, key = _decode_jit(
            params, cur, kc, vc, pos, key, n_heads=n_heads, dtype=dtype,
            temperature=float(temperature), top_k=int(top_k))
        pending.append(cur)
    # ONE D2H for all sampled tokens: per-token np.asarray would pay a
    # host sync and a transfer round-trip each
    if pending:
        out.append(np.asarray(jnp.stack(pending, axis=1)))
    return np.concatenate(out, axis=1)


@register_model("transformer")
def build(d_model: int = 64, n_heads: int = 4, n_layers: int = 2,
          vocab: int = 256, max_len: int = 128, batch: int = 1,
          n_kv_heads: int = 0, dtype: str = "float32", seed: int = 0):
    """Streaming-decode bundle: (ids, k_cache, v_cache, pos) →
    (logits, k_cache, v_cache, pos) — state loops through tensor_repo."""
    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.tensor.dtypes import DType
    from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec

    cdtype = jnp.dtype(dtype)
    n_kv = n_kv_heads or n_heads
    params = init_params(d_model=d_model, n_heads=n_heads,
                         n_layers=n_layers, vocab=vocab,
                         n_kv_heads=n_kv, seed=seed)
    hd = d_model // n_heads
    cshape = (n_layers, batch, max_len, n_kv, hd)

    def fn(params, ids, k_cache, v_cache, pos):
        return apply_step(params, ids, k_cache, v_cache, pos,
                          n_heads=n_heads, dtype=cdtype)

    in_spec = TensorsSpec.of(
        TensorInfo((batch, 1), DType.INT32, name="ids"),
        TensorInfo(cshape, DType.FLOAT32, name="k_cache"),
        TensorInfo(cshape, DType.FLOAT32, name="v_cache"),
        TensorInfo((1,), DType.INT32, name="pos"),
    )
    out_spec = TensorsSpec.of(
        TensorInfo((batch, vocab), DType.FLOAT32, name="logits"),
        TensorInfo(cshape, DType.FLOAT32, name="k_cache"),
        TensorInfo(cshape, DType.FLOAT32, name="v_cache"),
        TensorInfo((1,), DType.INT32, name="pos"),
    )
    return ModelBundle(fn=fn, params=params, in_spec=in_spec,
                       out_spec=out_spec, name="transformer")
