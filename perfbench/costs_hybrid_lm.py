"""Operations and bytes the hybrid decoder's two programs need (linear
attention with a carried state, block-sparse attention over compressed
keys), from shapes and from the counts a call's span carries (`costs.py`
has the dense family's, `costs_sparse_moe.py` the sparse-expert one's).

As there, these are the algorithm's needs at the precision the
configuration states: the weights once; each live sequence's state read
and written once a linear layer (float32, as the model keeps it); in a
sparse layer the compressed keys that are complete for the live context
once, the selected keys and values once, the step's own keys and values
written; logits out.  Operations: the matrix products, the state's update
and read (or, in a chunk, the scan's masked products within a run of
`SCAN` tokens and its two products with the state a run), the compressed
keys' scores and the selected attention.  The selection itself has no
term: it is the program's overhead and shows as a lower share.

A fourth family adds `costs_<family>.py` with the same functions
(`decode_step`, a prefill function, `kv_bytes_per_token`, and what a
sequence holds by slot where it keeps such state: `state_bytes_per_seq`,
`ckey_bytes_per_seq`) and hands their results
through its runner's `kernel_calls`.
"""

from __future__ import annotations

from typing import Tuple

from perfbench.costs import DTYPE_BYTES
from perfbench.references import hybrid_lm

SCAN = 256      # tokens a run of the chunked scan covers


def _layers(m: dict) -> Tuple[int, int]:
    return (m["kinds"].count(hybrid_lm.LINEAR),
            m["kinds"].count(hybrid_lm.SPARSE))


def _matrices(cfg: dict) -> int:
    """Matrix parameters of all layers."""
    m = hybrid_lm.dims(cfg)
    n = hybrid_lm.param_count(cfg)
    n_lin, n_sp = _layers(m)
    return sum(count * sum(v for k, v in n[kind].items() if k != "norms")
               for kind, count in ((hybrid_lm.LINEAR, n_lin),
                                   (hybrid_lm.SPARSE, n_sp)))


def state_bytes_per_seq(cfg: dict) -> int:
    """Bytes of one sequence's state: a float32 (hd x hd) a head and
    linear layer."""
    m = hybrid_lm.dims(cfg)
    return _layers(m)[0] * m["lh"] * m["lhd"] * m["lhd"] * 4


def ckey_bytes_per_seq(cfg: dict, max_len: int, dtype_bytes: int) -> int:
    """Bytes of one sequence's compressed keys as the program holds
    them, by slot: one a KV head, sparse layer and `stride` tokens of
    the longest sequence."""
    m = hybrid_lm.dims(cfg)
    entries = -(-int(max_len) // m["stride"])
    return _layers(m)[1] * m["hkv"] * m["hd"] * entries * dtype_bytes


def kv_bytes_per_token(cfg: dict, pool_dtype_bytes: int) -> int:
    """Pool bytes a token holds: K and V in the sparse layers."""
    m = hybrid_lm.dims(cfg)
    return _layers(m)[1] * 2 * m["hkv"] * m["hd"] * pool_dtype_bytes


def decode_step(cfg: dict, rows: int, ckeys_scored: int,
                kv_selected: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode step: `rows` sequences, one new token
    each, which in a sparse layer score `ckeys_scored` compressed keys
    and attend `kv_selected` positions in total (a KV head's count; the
    new ones included)."""
    m = hybrid_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    n_lin, n_sp = _layers(m)
    mats = _matrices(cfg) + m["d"] * m["vocab"]
    ops = 2.0 * rows * mats
    # S = lam S + k^T v, then q S: a multiply-add each, a state element
    ops += 4.0 * rows * n_lin * m["lh"] * m["lhd"] * m["lhd"]
    ops += 2.0 * ckeys_scored * m["h"] * m["hd"] * n_sp       # q . c
    ops += 4.0 * kv_selected * m["h"] * m["hd"] * n_sp        # QK^T, PV
    kv_row = 2 * m["hkv"] * m["hd"] * wb * n_sp               # K and V
    ck_row = m["hkv"] * m["hd"] * wb * n_sp
    nbytes = wb * mats + wb * rows * m["d"]       # embedding rows read
    nbytes += 2 * rows * state_bytes_per_seq(cfg)             # read, written
    nbytes += ck_row * ckeys_scored + kv_row * kv_selected
    nbytes += kv_row * rows                       # the step's own K and V
    nbytes += 4 * rows * m["vocab"]               # float32 logits written
    return ops, float(nbytes)


def prefill_chunk(cfg: dict, clen: int, pos0: int, ckeys_scored: int,
                  kv_selected: int) -> Tuple[float, float]:
    """(ops, bytes) of one chunk of `clen` prompt tokens at `pos0`, which
    yields the last token's logits.  `ckeys_scored` and `kv_selected` are
    summed over the chunk's queries (a KV head's count).  Keys, values
    and compressed keys of the context so far are read once (a query's
    selected blocks lie in it), the sequence's state once and written
    once."""
    m = hybrid_lm.dims(cfg)
    wb = DTYPE_BYTES[cfg["dtype"]]
    n_lin, n_sp = _layers(m)
    mats = _matrices(cfg)
    ops = 2.0 * clen * mats + 2.0 * m["d"] * m["vocab"]
    heads = n_lin * m["lh"]
    # within a run: Q K^T and P V over the causal half; a run: Q S and
    # K^T V, 2 x hd x hd a token and head each
    ops += 4.0 * heads * m["lhd"] * sum(
        n * (n + 1) / 2 for n in _runs(clen))
    ops += 4.0 * heads * m["lhd"] * m["lhd"] * clen
    ops += 2.0 * ckeys_scored * m["h"] * m["hd"] * n_sp
    ops += 4.0 * kv_selected * m["h"] * m["hd"] * n_sp
    ctx = pos0 + clen
    kv_row = 2 * m["hkv"] * m["hd"] * wb * n_sp
    ck_row = m["hkv"] * m["hd"] * wb * n_sp
    nbytes = wb * (mats + m["d"] * m["vocab"]) + wb * clen * m["d"]
    nbytes += 2 * state_bytes_per_seq(cfg)
    nbytes += kv_row * ctx + ck_row * (ctx // m["stride"])
    nbytes += 4 * m["vocab"]
    return ops, float(nbytes)


def _runs(clen: int):
    return [SCAN] * (clen // SCAN) + ([clen % SCAN] if clen % SCAN else [])
