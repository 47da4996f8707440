"""A tiny configuration of the latent family for the CPU tests (the
benchmark's `tiny.py` is left as it is): 4 heads of 8 + 4 (keys) and 8
(values) through ranks 24 (queries) and 16 (the latent), YaRN over an
original length of 16 so that positions here reach past it, blocks of 4 and
chunks of 8, the first layer dense, 8 groups of 2 published experts of
which group 2's pair is held, 3 groups and 4 experts a token."""

from __future__ import annotations

import copy

import tiny
from perfbench import harness

CONFIG = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "n_group": 8,
    "n_routed_experts": 2, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "q_lora_rank": 24, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 8,
    "vocab_size": 256,
    "expert_share": {"published": 16, "first": 4},
    "runner": "latent_moe_llm", "reference": "latent_moe_lm",
    "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 4,
                "paged_kernel": "xla", "prefill_chunk": 8, "chunk_every": 1,
                "eos_id": -1, "admit_window_ms": 0.5, "num_blocks": 80},
    "kernels": {"decode_step": "jit_latent_moe_decode_step",
                "prefill": "jit_latent_moe_prefill_chunk"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

# Every prompt is longer than the chunk (8), as tiny_sparse_moe says why;
# one in two chunks, longer ones; totals under max_len.
CODE_BACKLOG = {
    "unit": "request",
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[9, 3], [12, 6], [20, 8], [33, 5]],
}


def cell(config: dict = CONFIG, traffic: dict = CODE_BACKLOG
         ) -> harness.Cell:
    c = tiny.cell(copy.deepcopy(config), copy.deepcopy(traffic))
    c.name = "tiny-latent-moe"
    return c
