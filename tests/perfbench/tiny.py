"""A tiny configuration and mix for the CPU tests of the harness: the same
runner and readers as on the chip, at a size a test run can hold."""

from __future__ import annotations

import copy

from perfbench import harness

LM_CONFIG = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 160,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "vocab_size": 256,
    "runner": "llm", "reference": "decoder_lm", "dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 64, "block_size": 8,
                "paged_kernel": "xla", "eos_id": -1, "admit_window_ms": 0.5,
                "num_blocks": 40},
    "kernels": {"decode_step": "jit_paged_decode_step",
                "prefill": "jit_paged_prefill"},
    "check": {"sample_requests": 3, "controls": ["int8"],
              "limits": {"served_token_gap_max": 1e-4}},
}

CHAT_BACKLOG = {
    "unit": "request",
    # more than a fast host can finish inside a test's window
    "arrival": {"mode": "backlog", "ramp_s": 0.2, "base": 4,
                "per_second": 2000.0},
    "items": [[5, 6], [9, 4], [12, 8], [20, 5]],
}


def cell(config: dict = LM_CONFIG, traffic: dict = CHAT_BACKLOG,
         metric: str = "tokens_per_s") -> harness.Cell:
    """A cell that reports `metric` and ``setup_s``, and no per-layer
    metric (those need a device trace)."""
    e2e = [{"name": metric, "unit": "x"}, {"name": "setup_s", "unit": "s"}]
    return harness.Cell("tiny", 1, "tiny-" + config["runner"], "tiny-traffic",
                        copy.deepcopy(config), copy.deepcopy(traffic),
                        e2e, [])
