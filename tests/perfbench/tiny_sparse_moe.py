"""A tiny configuration of the sparse-expert family for the CPU tests (the
benchmark's `tiny.py` is left as it is): the query width is twice the
hidden size, as at the published sizes."""

from __future__ import annotations

import copy

import tiny
from perfbench import harness

CONFIG = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 192,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
    "vocab_size": 256,
    "runner": "sparse_moe_llm", "reference": "sparse_moe_lm",
    "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 8,
                "paged_kernel": "xla", "prefill_chunk": 8, "eos_id": -1,
                "admit_window_ms": 0.5, "num_blocks": 40},
    "kernels": {"decode_step": "jit_sparse_moe_decode_step",
                "prefill": "jit_sparse_moe_prefill_chunk"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

# Every prompt is longer than the chunk (8), as in the cell: a prompt that
# fits one chunk is prefilled at once and may finish before an earlier
# long one, still in its chunks, has its first token, which the harness
# counts as passed over. Totals stay under max_len.
LONGCTX_BACKLOG = {
    "unit": "request",
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[9, 6], [12, 4], [20, 8], [33, 5]],
}


def cell(config: dict = CONFIG, traffic: dict = LONGCTX_BACKLOG
         ) -> harness.Cell:
    c = tiny.cell(copy.deepcopy(config), copy.deepcopy(traffic))
    c.name = "tiny-sparse-moe"
    return c
