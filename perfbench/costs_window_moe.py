"""Operations and bytes the window family's two programs need, from shapes
and from the counts a call's span carries (`costs.py` has the dense
family's; a new kernel adds its functions in a file of its own).

As there, these are the algorithm's needs at the precision the
configuration states: the weights outside the routed experts and the head
once, the three matrices of each held expert that got a token once
(`experts_touched`: distinct held experts with a token, summed over the
expert layers), a full layer's live context and a window layer's newest
`sliding_window` positions of it once, logits out; the products of the
(token, expert) pairs held here, and attention over what the causal edge
and the window allow.  Pairs routed to experts that are not held cost
nothing and are not counted.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.costs import DTYPE_BYTES
from perfbench.references import window_moe_lm


def _layers(m: dict) -> Tuple[int, int, int]:
    """(full layers, window layers, expert layers)."""
    n_window = sum(k == window_moe_lm.SLIDING for k in m["kinds"])
    return m["layers"] - n_window, n_window, m["layers"] - m["dense"]


def _outside_experts(cfg: dict, m: dict) -> int:
    """Matrix parameters of all layers outside their routed experts."""
    n = window_moe_lm.param_count(cfg)
    return (m["layers"] * n["attention"] + m["dense"] * n["dense_mlp"]
            + (m["layers"] - m["dense"]) * (n["shared"] + n["router"]))


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return (window_moe_lm.param_count(cfg)["expert"]
            * DTYPE_BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int, kind: str) -> int:
    """Pool bytes a token holds in the pools of the layers of `kind`
    ("full" or "window"): K and V, every layer of the kind."""
    m = window_moe_lm.dims(cfg)
    n_full, n_window, _ = _layers(m)
    layers = n_full if kind == "full" else n_window
    return 2 * layers * m["hkv"] * m["hd"] * pool_dtype_bytes


def window_cap(cfg: dict, span: int, block_size: int) -> int:
    """The most window blocks a sequence holds while `span` consecutive
    queries of it are computed at once: the program's own rule
    (`paged_cache.window_cap`), restated for the sizing of the pools."""
    w = window_moe_lm.dims(cfg)["window"]
    return -(-(w - 1 + span) // block_size) + 1


def window_pool_blocks(cfg: dict, rows: int, chunk: int,
                       block_size: int) -> int:
    """Blocks of the window layers' pools as the program sizes them:
    every row at its decode cap, what one prompt chunk's cap adds, and
    the scratch block."""
    one = window_cap(cfg, 1, block_size)
    return rows * one + window_cap(cfg, chunk, block_size) - one + 1


def decode_step(cfg: dict, rows: int, kv_full: int, kv_window: int,
                experts_touched: int, pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, a full layer attending `kv_full` cached positions in total and
    a window layer `kv_window` (the new ones included), `pairs_held`
    (token, expert) pairs at experts held here."""
    m = window_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    n_full, n_window, _ = _layers(m)
    mats = _outside_experts(cfg, m) + m["d"] * m["vocab"]
    attended = kv_full * n_full + kv_window * n_window
    ops = 2.0 * rows * mats + 2.0 * pairs_held * 3 * m["d"] * m["f"]
    ops += 4.0 * attended * m["h"] * m["hd"]                # QK^T, PV
    kv_row = 2 * m["hkv"] * m["hd"] * wb                    # K and V, a layer
    nbytes = wb * mats + experts_touched * expert_bytes(cfg)
    nbytes += wb * rows * m["d"]                  # embedding rows read
    nbytes += kv_row * attended
    nbytes += kv_row * rows * m["layers"]         # the step's own K and V
    nbytes += 4 * rows * m["vocab"]               # float32 logits written
    return ops, float(nbytes)


def prefill_chunk(cfg: dict, clen: int, pos0: int, experts_touched: int,
                  pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one chunk of `clen` prompt tokens starting at
    position `pos0`, which yields the last token's logits: a full layer's
    queries attend everything up to themselves, a window layer's the
    newest `sliding_window` of it; keys and values of what they may
    attend once."""
    m = window_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    n_full, n_window, _ = _layers(m)
    w = m["window"]
    mats = _outside_experts(cfg, m)
    full = clen * pos0 + clen * (clen + 1) / 2
    window = sum(min(pos0 + i + 1, w) for i in range(clen))
    ops = 2.0 * clen * mats + 2.0 * m["d"] * m["vocab"]
    ops += 2.0 * pairs_held * 3 * m["d"] * m["f"]
    ops += 4.0 * (full * n_full + window * n_window) * m["h"] * m["hd"]
    kv_row = 2 * m["hkv"] * m["hd"] * wb
    nbytes = wb * (mats + m["d"] * m["vocab"])
    nbytes += experts_touched * expert_bytes(cfg)
    nbytes += wb * clen * m["d"]
    nbytes += kv_row * ((pos0 + clen) * n_full
                        + min(pos0 + clen, w - 1 + clen) * n_window)
    nbytes += 4 * m["vocab"]
    return ops, float(nbytes)
