"""Operations and bytes the sparse-expert decoder's two programs need,
from shapes and from the counts a call's span carries (`costs.py` has the
dense family's; a new kernel adds its functions in a file of its own).

As there, these are the algorithm's needs at the precision the
configuration states: the weights outside the experts once, the three
matrices of each expert that got a token once (`experts_touched`: distinct
experts with a token, summed over layers), the indexer's keys of the live
context once and the selected keys and values once, logits out.  The exact
selection has no term: it is the program's overhead and shows as a lower
share.

A third family adds `costs_<family>.py` with the same three functions
(`decode_step`, a prefill function, `kv_bytes_per_token`) and hands their
results through its runner's `kernel_calls`.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.costs import DTYPE_BYTES
from perfbench.references import sparse_moe_lm


def _outside_experts(m: dict) -> int:
    """Matrix parameters of one layer outside its experts."""
    qw, kw = m["h"] * m["hd"], m["hkv"] * m["hd"]
    return (m["d"] * (qw + 2 * kw) + qw * m["d"]
            + m["d"] * (m["hi"] * m["di"] + m["di"] + m["hi"])
            + m["d"] * m["e"])


def expert_bytes(cfg: dict) -> int:
    """Bytes of one expert's three matrices."""
    m = sparse_moe_lm.dims(cfg)
    return 3 * m["d"] * m["f"] * DTYPE_BYTES[cfg["dtype"]]


def decode_step(cfg: dict, rows: int, kv_tokens: int, kv_selected: int,
                experts_touched: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, whose indexers score `kv_tokens` cached positions in total
    (the new ones included) and which attend `kv_selected` of them."""
    m = sparse_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = m["layers"] * _outside_experts(m) + m["d"] * m["vocab"]
    ops = 2.0 * rows * mats
    ops += 2.0 * rows * m["k"] * 3 * m["d"] * m["f"] * m["layers"]
    ops += 2.0 * kv_tokens * m["hi"] * m["di"] * m["layers"]    # qI . kI
    ops += 4.0 * kv_selected * m["h"] * m["hd"] * m["layers"]   # QK^T, PV
    kv_row = 2 * m["hkv"] * m["hd"] * wb * m["layers"]          # K and V
    idx_row = m["di"] * wb * m["layers"]
    nbytes = wb * mats + experts_touched * expert_bytes(cfg)
    nbytes += wb * rows * m["d"]                  # embedding rows read
    nbytes += idx_row * kv_tokens + kv_row * kv_selected
    nbytes += (kv_row + idx_row) * rows           # the step's own state
    nbytes += 4 * rows * m["vocab"]               # float32 logits written
    return ops, float(nbytes)


def prefill_chunk(cfg: dict, clen: int, pos0: int,
                  experts_touched: int) -> Tuple[float, float]:
    """(ops, bytes) of one chunk of `clen` prompt tokens starting at
    position `pos0`, which yields the last token's logits: the indexer's
    product over the context so far, attention over min(context, topk)
    keys a query, keys and values of the live context once."""
    m = sparse_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = m["layers"] * _outside_experts(m)
    scored = clen * pos0 + clen * (clen + 1) / 2
    attended = sum(min(pos0 + i + 1, m["topk"]) for i in range(clen))
    ops = 2.0 * clen * mats + 2.0 * m["d"] * m["vocab"]
    ops += 2.0 * clen * m["k"] * 3 * m["d"] * m["f"] * m["layers"]
    ops += 2.0 * scored * m["hi"] * m["di"] * m["layers"]
    ops += 4.0 * attended * m["h"] * m["hd"] * m["layers"]
    state_row = (2 * m["hkv"] * m["hd"] + m["di"]) * wb * m["layers"]
    nbytes = wb * (mats + m["d"] * m["vocab"])
    nbytes += experts_touched * expert_bytes(cfg)
    nbytes += wb * clen * m["d"] + state_row * (pos0 + clen)
    nbytes += 4 * m["vocab"]
    return ops, float(nbytes)


def kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int) -> int:
    """Pool bytes a token holds: K, V and the indexer's key, every
    layer."""
    m = sparse_moe_lm.dims(cfg)
    return (m["layers"] * (2 * m["hkv"] * m["hd"] + m["di"])
            * pool_dtype_bytes)
