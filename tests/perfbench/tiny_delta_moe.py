"""A tiny configuration of the delta family for the CPU tests (the
benchmark's `tiny.py` is left as it is): five layers K K L K L, the first
dense; KDA of 2 heads of 8 with a convolution over 4 tokens; latent
attention of 4 heads of 8 + 4 (keys) and 8 (values) through a latent of 16,
no query rank and no rope; blocks of 4 and chunks of 8; 16 published
experts of which 4 from the 4th on are held, 4 a token."""

from __future__ import annotations

import copy

import tiny
from perfbench import harness

CONFIG = {
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 160, "kv_lora_rank": 16,
    "linear_attn_config": {"full_attn_layers": [3, 5], "head_dim": 8,
                           "kda_layers": [1, 2, 4], "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 32,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_token": 4,
    "num_hidden_layers": 5, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.446, "v_head_dim": 8, "vocab_size": 256,
    "expert_share": {"published": 16, "first": 4},
    "runner": "delta_moe_llm", "reference": "delta_moe_lm",
    "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 4,
                "paged_kernel": "xla", "prefill_chunk": 8, "chunk_every": 1,
                "eos_id": -1, "admit_window_ms": 0.5, "num_blocks": 80},
    "kernels": {"decode_step": "jit_delta_moe_decode_step",
                "prefill": "jit_delta_moe_prefill_chunk"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

# Every prompt is longer than the chunk (8), as tiny_sparse_moe says why;
# one in two chunks, longer ones; totals under max_len.
THINK_BACKLOG = {
    "unit": "request",
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[9, 6], [12, 9], [20, 8], [33, 12]],
}


def cell(config: dict = CONFIG, traffic: dict = THINK_BACKLOG
         ) -> harness.Cell:
    c = tiny.cell(copy.deepcopy(config), copy.deepcopy(traffic))
    c.name = "tiny-delta-moe"
    return c
