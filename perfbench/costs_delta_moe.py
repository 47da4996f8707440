"""Operations and bytes the delta family's two programs need, from shapes
and from the counts a call's span carries (`costs.py` has the dense
family's; a new kernel adds its functions in a file of its own).

As there, these are the algorithm's needs at the precision the
configuration states: the weights outside the routed experts and the head
once, the three matrices of each held expert that got a token once
(`experts_touched`: distinct held experts with a token, summed over the
expert layers), **each live row's state read and written once in float32
and its convolutions' tails once at the compute type's bytes**, the latent
layers' live rows once at the pool's bytes, logits out; the products of the
(token, expert) pairs held here, of the projections, of the delta rule and
of the latent attention in its cheaper form
(`costs_latent_moe.attend_ops`' rule, over the latent layers alone).  Pairs
routed to experts that are not held cost nothing and are not counted.

The delta rule's operations are the recurrence's, a token a head: the decay
(d^2), what the state holds of k (2 d^2), the rank-one write (2 d^2) and
the read by q (2 d^2).  A chunk's closed form over runs of 64 costs a
little more (6 d^2 + 4 x 64 d) and is the program's choice, not the
algorithm's need.  `decode_delta` / `chunk_delta` are the KDA layers' part
alone (the rule, the convolutions, the state and the tails: not the
projections), for ``layer_metrics/delta_share_pct.py``.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.costs import DTYPE_BYTES
from perfbench.costs_latent_moe import attend_ops
from perfbench.references import delta_moe_lm


def _layers(m: dict) -> Tuple[int, int]:
    """(KDA layers, latent layers)."""
    return (m["kinds"].count(delta_moe_lm.KDA),
            m["kinds"].count(delta_moe_lm.LATENT))


def _outside_experts(cfg: dict, m: dict) -> int:
    """Matrix parameters of all layers outside their routed experts."""
    n = delta_moe_lm.param_count(cfg)
    kda, latent = _layers(m)
    return (kda * n["kda"] + latent * n["latent"]
            + m["dense"] * n["dense_mlp"]
            + (m["layers"] - m["dense"]) * (n["shared"] + n["router"]))


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return (delta_moe_lm.param_count(cfg)["expert"]
            * DTYPE_BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int) -> int:
    """Pool bytes a token holds by block: one latent and one shared key a
    latent layer."""
    m = delta_moe_lm.dims(cfg)
    return _layers(m)[1] * (m["rkv"] + m["rope"]) * pool_dtype_bytes


def state_bytes_per_seq(cfg: dict) -> int:
    """Bytes of one sequence's float32 state, all KDA layers."""
    m = delta_moe_lm.dims(cfg)
    return _layers(m)[0] * m["kh"] * m["kd"] * m["kd"] * 4


def tail_bytes_per_seq(cfg: dict, dtype_bytes: int) -> int:
    """Bytes of one sequence's convolution tails, all KDA layers: the
    last K - 1 inputs of q, k and v."""
    m = delta_moe_lm.dims(cfg)
    return (_layers(m)[0] * (m["conv"] - 1) * 3 * m["kh"] * m["kd"]
            * dtype_bytes)


def slot_bytes_per_seq(cfg: dict) -> int:
    """What a sequence holds by slot: its state and its tails."""
    return state_bytes_per_seq(cfg) + tail_bytes_per_seq(
        cfg, DTYPE_BYTES[cfg["dtype"]])


def _delta_ops(m: dict, tokens: int) -> float:
    """The rule's and the convolutions' operations for `tokens` tokens,
    all KDA layers."""
    rule = 7.0 * m["kh"] * m["kd"] * m["kd"]
    conv = 2.0 * m["conv"] * 3 * m["kh"] * m["kd"]
    return _layers(m)[0] * tokens * (rule + conv)


def decode_delta(cfg: dict, rows: int) -> Tuple[float, float]:
    """(ops, bytes) of a decode step's KDA part alone: each of `rows`
    rows' state and tails read and written once."""
    m = delta_moe_lm.dims(cfg)
    return _delta_ops(m, rows), 2.0 * rows * slot_bytes_per_seq(cfg)


def chunk_delta(cfg: dict, clen: int, fresh: bool) -> Tuple[float, float]:
    """(ops, bytes) of a chunk's KDA part alone: one sequence's state
    and tails written once, and read once unless the chunk is its first."""
    m = delta_moe_lm.dims(cfg)
    return _delta_ops(m, clen), (2.0 - bool(fresh)) * slot_bytes_per_seq(cfg)


def decode_attention(cfg: dict, rows: int, kv_tokens: int
                     ) -> Tuple[float, float]:
    """(ops, bytes) of a decode step's scores and sums alone, the latent
    layers: `kv_tokens` cached positions attended in total (the new ones
    included), their latents and shared keys read once."""
    m = delta_moe_lm.dims(cfg)
    n = _layers(m)[1]
    row = (m["rkv"] + m["rope"]) * DTYPE_BYTES[cfg["dtype"]]
    return (n * attend_ops(m, kv_tokens, rows, kv_tokens),
            float(n * row * (kv_tokens + rows)))


def chunk_attention(cfg: dict, clen: int, pos0: int) -> Tuple[float, float]:
    """(ops, bytes) of a chunk's scores, sums and expansion alone, the
    latent layers: every query attends everything up to itself."""
    m = delta_moe_lm.dims(cfg)
    n = _layers(m)[1]
    row = (m["rkv"] + m["rope"]) * DTYPE_BYTES[cfg["dtype"]]
    pairs = clen * pos0 + clen * (clen + 1) / 2
    return (n * attend_ops(m, pairs, clen, pos0 + clen),
            float(n * row * (pos0 + clen)))


def decode_step(cfg: dict, rows: int, kv_tokens: int, experts_touched: int,
                pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, a latent layer attending `kv_tokens` cached positions in total,
    `pairs_held` (token, expert) pairs at experts held here."""
    m = delta_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = _outside_experts(cfg, m) + m["d"] * m["vocab"]
    a_ops, a_bytes = decode_attention(cfg, rows, kv_tokens)
    d_ops, d_bytes = decode_delta(cfg, rows)
    ops = (2.0 * rows * mats + 2.0 * pairs_held * 3 * m["d"] * m["f"]
           + a_ops + d_ops)
    nbytes = wb * mats + experts_touched * expert_bytes(cfg)
    nbytes += wb * rows * m["d"]                  # embedding rows read
    nbytes += a_bytes + d_bytes       # live latents; states and tails
    nbytes += 4 * rows * m["vocab"]               # float32 logits written
    return ops, float(nbytes)


def prefill_chunk(cfg: dict, clen: int, pos0: int, experts_touched: int,
                  pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one chunk of `clen` prompt tokens starting at
    position `pos0`, which yields the last token's logits."""
    m = delta_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = _outside_experts(cfg, m)
    a_ops, a_bytes = chunk_attention(cfg, clen, pos0)
    d_ops, d_bytes = chunk_delta(cfg, clen, pos0 == 0)
    ops = 2.0 * clen * mats + 2.0 * m["d"] * m["vocab"] + a_ops + d_ops
    ops += 2.0 * pairs_held * 3 * m["d"] * m["f"]
    nbytes = wb * (mats + m["d"] * m["vocab"])
    nbytes += experts_touched * expert_bytes(cfg)
    nbytes += wb * clen * m["d"] + a_bytes + d_bytes + 4 * m["vocab"]
    return ops, float(nbytes)
