"""W8A8 quantized matmul path for the transformer (MXU int8).

On v5e the MXU runs int8×int8→int32 at ~2× the bf16 rate (measured
376–496 TOP/s vs the 197 TFLOP/s bf16 peak — bench `mxu_peak`), but
int8 NHWC *convolutions* lose to relayout costs on this backend, so the
int8 story here targets what actually wins: the transformer's large
matmuls. Weights are quantized per-output-channel (symmetric int8),
activations per-token at runtime (dynamic symmetric int8 — one amax +
scale per row, fused by XLA into the surrounding elementwise work), and
the int32 accumulator is rescaled in f32. Attention stays in bf16
(the flash kernel path); RMSNorm/softmax/rope stay f32/bf16 — only the
MXU-bound projections change.

This mirrors the role of the reference's quantized execution providers
(`tensor_filter_tensorrt.cc` int8 calibration, `tensor_filter_snpe`
quantized DLCs): quantization as an execution feature with the accuracy
contract checked against the float path (tests).

**Measured perf reality on v5e**: the int8 dot itself runs ~2-3× the
bf16 rate at transformer shapes, and the former bottleneck — the
dynamic activation-quant pass, which as plain XLA ops made ~3 HBM
trips over the activations and cost more than the matmul it fed
(0.62 ms vs 0.13 ms at 16384×1024; round 4 measured the whole W8A8
matmul at 0.74× bf16 because of it) — is now a single-VMEM-pass
Pallas kernel (`backends/pallas_ops.quantize_rows`). With it the full
W8A8 matmul measures **1.9× the bf16 matmul** (0.37 vs 0.71 ms at
16384×1024×3072, round 5): W8A8 is a genuine perf path for MXU-bound
projections, not just an accuracy-verified capability. Int8
*convolutions* still lose to relayout on this backend, so
tflite_quant.py keeps dequantize→bf16 as its conv default.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def quantize_weight(w, axis: int = 1):
    """Symmetric per-output-channel int8 quantization of a 2-D weight.

    `axis` is the OUTPUT dim (scales broadcast over it on dequant)."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=1 - axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_transformer(params: Dict[str, Any]) -> Dict[str, Any]:
    """Float transformer params → W8A8 params: every large matmul
    weight (wqkv/wo/wi/wd/head) becomes (int8, per-col scale); norms,
    embeddings and everything small stay float."""
    out: Dict[str, Any] = {"embed": params["embed"],
                           "ln_f": params["ln_f"], "blocks": []}
    for blk in params["blocks"]:
        qblk = {"ln1": blk["ln1"], "ln2": blk["ln2"]}
        for name in ("wqkv", "wo", "wi", "wd"):
            q, s = quantize_weight(blk[name])
            qblk[name] = q
            qblk[f"{name}_scale"] = s
        out["blocks"].append(qblk)
    q, s = quantize_weight(params["head"])
    out["head"], out["head_scale"] = q, s
    return out


def w8a8_matmul(x, w_q, w_scale):
    """(…, K) f32/bf16 × int8 (K, N) → (…, N) f32.

    Dynamic per-row activation quantization (the Pallas single-pass
    `quantize_rows` kernel), int8×int8→int32 on the MXU, one fused
    rescale. Expressed in plain XLA the quant pass made ~3 HBM trips
    over the activations and cost more than the int8 dot it feeds;
    with the fused kernel the whole W8A8 matmul measured **1.9× the
    bf16 matmul** at 16384×1024×3072 on v5e (0.37 vs 0.71 ms, round
    5) — see the perf-reality note in the module docstring. Row counts
    that can't tile the kernel fall back to the equivalent XLA
    expression inside quantize_rows itself."""
    from nnstreamer_tpu.backends.pallas_ops import quantize_rows

    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    x_q, x_scale = quantize_rows(x2)
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale * w_scale.reshape(1, -1)
    return out.reshape(lead + (out.shape[-1],))


def apply_seq_w8a8(params_q, ids, *, n_heads=4, attn: str = "auto",
                   dtype=jnp.float32):
    """Full-sequence forward with W8A8 projections — the quantized twin
    of transformer.apply_seq (same block structure, same attention
    kernels; only the big matmuls run int8).

    `dtype` is the inter-op activation dtype, exactly like apply_seq's:
    pass bfloat16 for the perf path — the int8 matmuls don't care (they
    re-quantize their input rows), but f32 activations double every
    residual/norm/attention HBM trip between them (measured: the f32
    default ran a d=1024 prefill SLOWER than bf16 apply_seq even with
    each matmul 1.9× faster; bf16 activations let the matmul win
    through, see PARITY)."""
    from nnstreamer_tpu.models import transformer as T
    from nnstreamer_tpu.parallel.ring_attention import reference_attention

    b, s = ids.shape
    x = params_q["embed"][ids].astype(dtype)
    pos = jnp.arange(s)
    use_pallas = attn == "pallas" or (attn == "auto" and s % 128 == 0)
    for blk in params_q["blocks"]:
        h = T.rmsnorm(x, blk["ln1"].astype(dtype))
        qkv = w8a8_matmul(h, blk["wqkv"], blk["wqkv_scale"]).astype(dtype)
        d = x.shape[-1]
        hd = d // n_heads
        kv_dim = (qkv.shape[-1] - d) // 2
        n_kv = kv_dim // hd
        q = qkv[..., :d].reshape(b, s, n_heads, hd)
        k = qkv[..., d:d + kv_dim].reshape(b, s, n_kv, hd)
        v = qkv[..., d + kv_dim:].reshape(b, s, n_kv, hd)
        q, k = T.rope(q, pos), T.rope(k, pos)
        k, v = T.expand_kv(k, n_heads), T.expand_kv(v, n_heads)
        if use_pallas:
            from nnstreamer_tpu.backends.pallas_ops import flash_attention

            attn_out = flash_attention(q.astype(jnp.bfloat16),
                                       k.astype(jnp.bfloat16),
                                       v.astype(jnp.bfloat16),
                                       causal=True)
        else:
            attn_out = reference_attention(q, k, v, causal=True)
        attn_out = attn_out.reshape(b, s, -1).astype(dtype)
        x = x + w8a8_matmul(attn_out, blk["wo"],
                            blk["wo_scale"]).astype(dtype)
        h = T.rmsnorm(x, blk["ln2"].astype(dtype))
        gate_up = w8a8_matmul(h, blk["wi"], blk["wi_scale"]).astype(dtype)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        x = x + w8a8_matmul(jax.nn.silu(gate) * up, blk["wd"],
                            blk["wd_scale"]).astype(dtype)
    x = T.rmsnorm(x, params_q["ln_f"].astype(dtype))
    return w8a8_matmul(x, params_q["head"], params_q["head_scale"])


def apply_step_w8a8(params_q, ids, k_cache, v_cache, pos, *, n_heads=4,
                    dtype=jnp.bfloat16):
    """One streaming decode step with W8A8 projections — the quantized
    twin of transformer.apply_step, sharing the float path's exact body
    (`transformer._step_impl`: ring-slot write-through, RoPE, GQA
    expansion, f32 softmax); only the five projection matmuls differ.

    At decode the matmuls are skinny (M = batch rows): the win is the
    int8 WEIGHTS halving the per-step weight sweep — measured round 5
    at d=1024/4L/B=8 (scan-timed, subprocess-isolated builder probes):
    0.104 vs 0.132 ms/step at max_len=256 (+26%, 77k tok/s) and 0.80
    vs 0.91 at max_len=2048 (+13%) where the bf16 KV sweep takes a
    larger share. The driver-capturable `w8a8_decode` bench row runs
    the max_len=2048 point. `dtype` is the inter-op activation dtype
    (bf16 default — the f32 lesson from apply_seq_w8a8 applies here
    too)."""
    from nnstreamer_tpu.models.transformer import _step_impl

    def proj(store, name, x):
        out = w8a8_matmul(x, store[name], store[f"{name}_scale"])
        return out.astype(dtype)

    return _step_impl(params_q, ids, k_cache, v_cache, pos, n_heads,
                      dtype, proj)
