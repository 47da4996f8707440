"""Operations and bytes a call needs, computed from shapes alone.

These are the algorithm's needs at the precision the configuration states,
not what a particular program moves: weights and inputs read once, outputs
written once, keys and values of the live context read once.  A roofline
share divides ``max(ops / peak_ops, bytes / peak_bytes)`` by the device time
of the program that did the call.
"""

from __future__ import annotations

import json
import os
from typing import Tuple

from perfbench.references import decoder_lm

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks_for(device_kind: str) -> dict:
    """Peak rates of one chip; a kind that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in perfbench/peaks.json"
            f" (known: {sorted(table)}); add a row with its source")
    return table[device_kind]


def floor_seconds(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# -- decoder language model ------------------------------------------------

def _lm_layer_weights(m: dict) -> int:
    kv = m["hkv"] * m["hd"]
    return (m["d"] * (m["d"] + 2 * kv) + m["d"] * m["d"]
            + m["d"] * 2 * m["f"] + m["f"] * m["d"])


def lm_decode_step(cfg: dict, rows: int, kv_tokens: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, attending `kv_tokens` cached positions in total (the new ones
    included)."""
    m = decoder_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    layer_w = _lm_layer_weights(m)
    ops = 2.0 * rows * (m["layers"] * layer_w + m["d"] * m["vocab"])
    ops += 4.0 * kv_tokens * m["h"] * m["hd"] * m["layers"]   # QK^T and PV
    kv_row = 2 * m["layers"] * m["hkv"] * m["hd"] * wb         # K and V
    nbytes = wb * (m["layers"] * layer_w + m["d"] * m["vocab"])
    nbytes += wb * rows * m["d"]                # embedding rows read
    nbytes += kv_row * kv_tokens                # context read
    nbytes += kv_row * rows                     # new keys and values written
    nbytes += 4 * rows * m["vocab"]             # float32 logits written
    return ops, float(nbytes)


def lm_prefill(cfg: dict, prompt_len: int) -> Tuple[float, float]:
    """(ops, bytes) of one whole-prompt prefill that yields the last
    position's logits and the prompt's keys and values."""
    m = decoder_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    layer_w = _lm_layer_weights(m)
    s = prompt_len
    ops = 2.0 * s * m["layers"] * layer_w + 2.0 * m["d"] * m["vocab"]
    # causal attention: s*(s+1)/2 query-key pairs, QK^T and PV
    ops += 4.0 * (s * (s + 1) / 2) * m["h"] * m["hd"] * m["layers"]
    kv_row = 2 * m["layers"] * m["hkv"] * m["hd"] * wb
    nbytes = wb * (m["layers"] * layer_w + m["d"] * m["vocab"])
    nbytes += wb * s * m["d"] + kv_row * s + 4 * m["vocab"]
    return ops, float(nbytes)


def lm_kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int) -> int:
    m = decoder_lm.dims(cfg)
    return 2 * m["layers"] * m["hkv"] * m["hd"] * pool_dtype_bytes
