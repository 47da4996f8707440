"""A tiny configuration of the hybrid family (linear attention with a
carried state, block-sparse attention over compressed keys) for the CPU
tests (the benchmark's `tiny.py` is left as it is): a sequence of more
than 24 tokens has more selection blocks than a query may attend."""

from __future__ import annotations

import copy

import tiny
from perfbench import harness

CONFIG = {
    "attn_use_rope": False, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128, "lightning_head_dim": 16, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_use_rope": True,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "qk_norm": True, "rms_norm_eps": 1e-06,
    "vocab_size": 256, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16,
    "published": {"num_hidden_layers": 32},
    "assumed_sizes": {"sparse_kernel_size": 8, "sparse_kernel_stride": 4,
                      "sparse_block_size": 8, "sparse_topk": 3,
                      "sparse_window_size": 8, "sparse_init_blocks": 1},
    "runner": "hybrid_llm", "reference": "hybrid_lm", "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 4,
                "paged_kernel": "xla", "prefill_chunk": 8, "chunk_every": 2,
                "eos_id": -1, "admit_window_ms": 0.5, "num_blocks": 80},
    "kernels": {"decode_step": "jit_hybrid_decode_step",
                "prefill": "jit_hybrid_prefill_chunk"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

# Every prompt is longer than the chunk (8), as in the cell (see
# tiny_sparse_moe.py); the longest runs past the 24 tokens of three
# selection blocks. Totals stay under max_len.
LONGDOC_BACKLOG = {
    "unit": "request",
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[9, 6], [12, 4], [21, 8], [41, 7]],
}


def cell(config: dict = CONFIG, traffic: dict = LONGDOC_BACKLOG
         ) -> harness.Cell:
    c = tiny.cell(copy.deepcopy(config), copy.deepcopy(traffic))
    c.name = "tiny-hybrid"
    return c
