"""A tiny configuration of the window family for the CPU tests (the
benchmark's `tiny.py` is left as it is): a window of 8 over blocks of 4 and
chunks of 4, two sliding layers to a full one, the first layer dense, 8
published experts of which 4 are held (from the third on), 2 a token."""

from __future__ import annotations

import copy

import tiny
from perfbench import harness

CONFIG = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 160,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_intermediate_size": 32, "mup_enabled": True,
    "num_attention_heads": 8, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_key_value_heads": 2, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 8,
    "vocab_size": 256,
    "expert_share": {"published": 8, "first": 2},
    "runner": "window_moe_llm", "reference": "window_moe_lm",
    "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 4,
                "paged_kernel": "xla", "prefill_chunk": 4, "chunk_every": 1,
                "eos_id": -1, "admit_window_ms": 0.5, "num_blocks": 48},
    "kernels": {"decode_step": "jit_window_moe_decode_step",
                "prefill": "jit_window_moe_prefill_chunk"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

# Every prompt is longer than the chunk (4), as tiny_sparse_moe says why;
# contexts that stay inside the window (5 + 2), cross it inside a chunk
# (20, 33) and cross it while decoding (7 + 6). Totals stay under max_len.
MIXED_BACKLOG = {
    "unit": "request",
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[5, 2], [7, 6], [20, 8], [33, 5]],
}


def cell(config: dict = CONFIG, traffic: dict = MIXED_BACKLOG
         ) -> harness.Cell:
    c = tiny.cell(copy.deepcopy(config), copy.deepcopy(traffic))
    c.name = "tiny-window-moe"
    return c
