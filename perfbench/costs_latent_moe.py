"""Operations and bytes the latent family's two programs need, from shapes
and from the counts a call's span carries (`costs.py` has the dense
family's; a new kernel adds its functions in a file of its own).

As there, these are the algorithm's needs at the precision the
configuration states: the weights outside the routed experts and the head
once, the three matrices of each held expert that got a token once
(`experts_touched`: distinct held experts with a token, summed over the
expert layers), the live latents and roped keys once at the pool's bytes,
logits out; the products of the (token, expert) pairs held here.  Pairs
routed to experts that are not held cost nothing and are not counted.

The attention's operations are counted too, since here they can bound a
call, **in the form with the fewer operations at the call's shape,
whichever the program ran** (`attend_ops`), so a program cannot raise its
share by choosing the dearer form.  One pass of a token through ``Wkvb``
is among the matrices every token passes (the absorbed form's queries and
outputs, or the expanded form's own keys and values); what the expanded
form pays beyond is the context's keys expanded again.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.costs import DTYPE_BYTES
from perfbench.references import latent_moe_lm


def _outside_experts(cfg: dict, m: dict) -> int:
    """Matrix parameters of all layers outside their routed experts."""
    n = latent_moe_lm.param_count(cfg)
    return (m["layers"] * n["attention"] + m["dense"] * n["dense_mlp"]
            + (m["layers"] - m["dense"]) * (n["shared"] + n["router"]))


def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return (latent_moe_lm.param_count(cfg)["expert"]
            * DTYPE_BYTES[cfg["dtype"]])


def kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int) -> int:
    """Pool bytes a token holds: one latent and one roped key a layer."""
    m = latent_moe_lm.dims(cfg)
    return m["layers"] * (m["rkv"] + m["rope"]) * pool_dtype_bytes


def attend_ops(m: dict, pairs: float, queries: int, keys: int) -> float:
    """Operations of one layer's scores and sums over `pairs` (query,
    key) pairs of `queries` queries and `keys` keys, in the cheaper form:
    absorbed, 2 H (2 rkv + rope) a pair; expanded, 2 H (nope + rope + v)
    a pair and 2 rkv H (nope + v) for each key that is not one of the
    call's own tokens."""
    absorbed = 2.0 * m["h"] * (2 * m["rkv"] + m["rope"]) * pairs
    expanded = (2.0 * m["h"] * (m["nope"] + m["rope"] + m["v"]) * pairs
                + 2.0 * m["rkv"] * m["h"] * (m["nope"] + m["v"])
                * max(keys - queries, 0))
    return min(absorbed, expanded)


def decode_attention(cfg: dict, rows: int, kv_tokens: int
                     ) -> Tuple[float, float]:
    """(ops, bytes) of a decode step's scores and sums alone, all layers:
    `kv_tokens` cached positions attended in total (the new ones
    included), their latents and roped keys read once."""
    m = latent_moe_lm.dims(cfg)
    row = (m["rkv"] + m["rope"]) * DTYPE_BYTES[cfg["dtype"]]
    return (m["layers"] * attend_ops(m, kv_tokens, rows, kv_tokens),
            float(m["layers"] * row * (kv_tokens + rows)))


def chunk_attention(cfg: dict, clen: int, pos0: int) -> Tuple[float, float]:
    """(ops, bytes) of a chunk's scores, sums and expansion alone, all
    layers: every query attends everything up to itself."""
    m = latent_moe_lm.dims(cfg)
    row = (m["rkv"] + m["rope"]) * DTYPE_BYTES[cfg["dtype"]]
    pairs = clen * pos0 + clen * (clen + 1) / 2
    return (m["layers"] * attend_ops(m, pairs, clen, pos0 + clen),
            float(m["layers"] * row * (pos0 + clen)))


def decode_step(cfg: dict, rows: int, kv_tokens: int, experts_touched: int,
                pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, attending `kv_tokens` cached positions in total, `pairs_held`
    (token, expert) pairs at experts held here."""
    m = latent_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = _outside_experts(cfg, m) + m["d"] * m["vocab"]
    a_ops, a_bytes = decode_attention(cfg, rows, kv_tokens)
    ops = 2.0 * rows * mats + 2.0 * pairs_held * 3 * m["d"] * m["f"] + a_ops
    nbytes = wb * mats + experts_touched * expert_bytes(cfg)
    nbytes += wb * rows * m["d"]                  # embedding rows read
    nbytes += a_bytes                 # live latents, and the step's own
    nbytes += 4 * rows * m["vocab"]               # float32 logits written
    return ops, float(nbytes)


def prefill_chunk(cfg: dict, clen: int, pos0: int, experts_touched: int,
                  pairs_held: int) -> Tuple[float, float]:
    """(ops, bytes) of one chunk of `clen` prompt tokens starting at
    position `pos0`, which yields the last token's logits."""
    m = latent_moe_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    mats = _outside_experts(cfg, m)
    a_ops, a_bytes = chunk_attention(cfg, clen, pos0)
    ops = 2.0 * clen * mats + 2.0 * m["d"] * m["vocab"] + a_ops
    ops += 2.0 * pairs_held * 3 * m["d"] * m["f"]
    nbytes = wb * (mats + m["d"] * m["vocab"])
    nbytes += experts_touched * expert_bytes(cfg)
    nbytes += wb * clen * m["d"] + a_bytes + 4 * m["vocab"]
    return ops, float(nbytes)
