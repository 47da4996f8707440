"""The benchmark's files for the sparse-expert family: the configuration
against its source, the parameter count, the cost functions against hand
counts, a whole tiny run through the new runner, the four new readers."""

import json
import os
import time

import pytest

import tiny_sparse_moe as tiny
from perfbench import costs, costs_sparse_moe, harness, run
from perfbench.layer_metrics import (expert_load_max_over_mean,
                                     experts_touched_avg,
                                     kv_read_over_needed,
                                     prefill_device_share_pct)
from perfbench.references import sparse_moe_lm

SEED = 2**31 + 29


def _keye():
    with open(os.path.join(harness.ROOT, "perfbench/configs",
                           "keye-vl-2.0-30b-a3b-6l.json")) as f:
        return json.load(f)


KEYE = _keye()

# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, the language model's keys
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_keye_file_holds_the_published_numbers():
    differ = {k for k, v in PUBLISHED.items() if KEYE.get(k, "absent") != v}
    assert differ == set(KEYE["reduced"]) == {"num_hidden_layers"}
    assert KEYE["num_hidden_layers"] == 6
    assert KEYE["source"].endswith("Keye-VL-2.0-30B-A3B/blob/main/config.json")
    for key in ("qk_norm", "indexer_rope", "indexer_score",
                "indexer_chunk_sizes", "mrope", "weights", "kv_pool_dtype",
                "vision_tower"):
        assert key in KEYE["assumed"]
    assert "8 TPU v5e chips in a pipeline of 6 layers" in KEYE["deployment"]
    assert KEYE["serving"] == {
        "max_batch": 8, "max_len": 33792, "block_size": 16,
        "paged_kernel": "xla", "prefill_chunk": 2048, "eos_id": -1,
        "admit_window_ms": 0.5, "pool_reserve_bytes": 2200000000}
    assert KEYE["check"]["sample_requests"] == 6 and KEYE["check"]["limits"]


def test_keye_parameter_count():
    n = sparse_moe_lm.param_count(KEYE)
    assert n["layer"] == {"attention": 18874368, "indexer": 2260992,
                          "router": 262144, "experts": 603979776,
                          "norms": 4352}
    assert n["per_layer"] == 625381632 and n["outside"] == 622329856
    total = 6 * n["per_layer"] + n["outside"] + n["final_norm"]
    assert 8.74e9 < 2 * total < 8.76e9              # bytes in bfloat16


def test_decode_step_hand_count():
    outside = 18874368 + 2260992 + 262144           # a layer, no experts
    mats = 6 * outside + 2048 * 151936
    ops, nbytes = costs_sparse_moe.decode_step(
        KEYE, rows=8, kv_tokens=140000, kv_selected=16384,
        experts_touched=312)
    assert ops == (2.0 * 8 * mats + 2.0 * 8 * 8 * 3 * 2048 * 768 * 6
                   + 2.0 * 140000 * 16 * 64 * 6
                   + 4.0 * 16384 * 32 * 128 * 6)
    kv_row, idx_row = 2 * 4 * 128 * 2 * 6, 64 * 2 * 6
    assert costs_sparse_moe.expert_bytes(KEYE) == 9437184
    assert nbytes == (2 * mats + 312 * 9437184 + 2 * 8 * 2048
                      + idx_row * 140000 + kv_row * 16384
                      + (kv_row + idx_row) * 8 + 4 * 8 * 151936)
    t, bound = costs.floor_seconds(ops, nbytes, costs.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 0.004 < t < 0.006


def test_prefill_chunk_hand_count():
    outside = 18874368 + 2260992 + 262144
    ops, nbytes = costs_sparse_moe.prefill_chunk(
        KEYE, clen=2048, pos0=14336, experts_touched=768)
    scored = 2048 * 14336 + 2048 * 2049 / 2
    assert ops == (2.0 * 2048 * 6 * outside + 2.0 * 2048 * 151936
                   + 2.0 * 2048 * 8 * 3 * 2048 * 768 * 6
                   + 2.0 * scored * 16 * 64 * 6
                   + 4.0 * 2048 * 2048 * 32 * 128 * 6)
    state_row = (2 * 4 * 128 + 64) * 2 * 6
    assert nbytes == (2 * (6 * outside + 2048 * 151936) + 768 * 9437184
                      + 2 * 2048 * 2048 + state_row * 16384 + 4 * 151936)
    # the first chunk of a prompt attends fewer than topk keys a query
    first, _ = costs_sparse_moe.prefill_chunk(KEYE, 2048, 0, 768)
    assert ops - first == (2.0 * 2048 * 14336 * 16 * 64 * 6 + 4.0 * (
        2048 * 2048 - 2048 * 2049 / 2) * 32 * 128 * 6)
    t, bound = costs.floor_seconds(ops, nbytes, costs.peaks_for("TPU v5 lite"))
    assert 0.008 < t < 0.016


def test_pool_bytes_per_token_of_the_float32_pool():
    assert costs_sparse_moe.kv_bytes_per_token(KEYE, 4) == 26112


def _run(seconds=2.5, trace=False, cell=None):
    import jax

    devs = jax.devices()[:1]
    line = run.run_cell(cell or tiny.cell(), SEED, seconds, trace, devs,
                        harness.device_info(devs), time.perf_counter())
    return json.loads(line)


def test_sound_tiny_run_is_correct(capsys):
    out = _run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"     # never a device metric
    printed = capsys.readouterr().out
    for name in ("requests_passed_over", "compiles_in_window",
                 "served_token_gap_max"):
        assert f"check {name}" in printed


def test_altered_token_is_not_correct(monkeypatch):
    from nnstreamer_tpu.llm import engine

    sound = engine.LLMEngine._sample
    count = [0]

    def broken(self, req, logits):
        count[0] += 1
        tok = sound(self, req, logits)
        return (tok + 1) % logits.shape[0] if count[0] % 7 == 0 else tok

    monkeypatch.setattr(engine.LLMEngine, "_sample", broken)
    cell = tiny.cell()
    cell.config["check"]["sample_requests"] = 1000      # every request
    assert _run(cell=cell)["correct"] is False


def test_trace_run_reports_the_new_counters_through_their_readers():
    cell = tiny.cell()
    names = ["experts_touched_avg.tokens", "kv_read_over_needed.tokens",
             "expert_load_max_over_mean.tokens", "decode_batch_avg.tokens",
             "kv_live_gb.tokens", "prefill_device_share_pct.tokens",
             "decode_step_roofline.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    got = _run(trace=True, cell=cell)["metrics"]
    assert 2.0 <= got["experts_touched_avg.tokens"]["value"] <= 8.0
    assert got["kv_read_over_needed.tokens"]["value"] >= 1.0
    assert got["expert_load_max_over_mean.tokens"]["value"] >= 1.0
    assert got["kv_live_gb.tokens"]["value"] > 0
    assert "decode_step_roofline.tokens" not in got     # no TPU plane


def test_a_program_without_the_family_fails_cleanly(monkeypatch):
    """What the parent commit does under this PR's benchmark files: the
    runner says so before any weight is made."""
    import sys

    monkeypatch.setitem(sys.modules, "nnstreamer_tpu.llm.spec", None)
    from perfbench.runners import sparse_moe_llm

    with pytest.raises(harness.HarnessError, match="no sparse-expert"):
        sparse_moe_llm.Runner(tiny.cell(), SEED, 1.0, False, [])


COUNTERS = {"counters": {
    "start": {"experts_touched_sum": 1000, "expert_steps_layers": 60,
              "kv_slots_read": 16384, "idx_slots_read": 270336,
              "kv_tokens_selected": 10000, "kv_tokens_scored": 100000},
    "end": {"experts_touched_sum": 4120, "expert_steps_layers": 120,
            "kv_slots_read": 16384 * 11, "idx_slots_read": 270336 * 11,
            "kv_tokens_selected": 170000, "kv_tokens_scored": 1500000}},
    "config": KEYE}


def test_counter_readers_on_planted_counters():
    assert experts_touched_avg.read(COUNTERS) == 52.0
    want = (163840 + 2703360 / 16) / (160000 + 1400000 / 16)
    assert kv_read_over_needed.read(COUNTERS) == pytest.approx(want)
    assert kv_read_over_needed.read({"counters": COUNTERS["counters"]}) is None


def test_span_reader_on_planted_chunk_spans():
    ctx = {"config": KEYE, "chunk_spans": [
        {"clen": 2048, "pos0": 0, "expert_load_max": 160,
         "experts_touched": 768},
        {"clen": 1024, "pos0": 2048, "expert_load_max": 96,
         "experts_touched": 760}]}
    # mean load of an expert: clen x 8 / 128
    assert expert_load_max_over_mean.read(ctx) == pytest.approx(
        (160 / 128 + 96 / 64) / 2)
    assert expert_load_max_over_mean.read({"config": KEYE}) is None


def test_prefill_share_reader_on_the_recorded_trace():
    from perfbench import xplane

    class TW:
        trace = xplane.read_xplane(os.path.join(
            harness.ROOT, "perfbench/data/recorded_step.xplane.pb"))
        start, end = 0.0, 1e18

    mods = {}
    for spans in TW.trace.modules.values():
        for n, _, d in spans:
            base = xplane.module_base(n)
            mods[base] = mods.get(base, 0.0) + d
    shares = [prefill_device_share_pct.read(
        {"trace_window": TW, "config": {"kernels": {"prefill": name}}})
        for name in sorted(mods)]
    assert all(0.0 < s <= 100.0 for s in shares)
    assert sum(shares) == pytest.approx(100.0)
    assert prefill_device_share_pct.read(
        {"trace_window": TW,
         "config": {"kernels": {"prefill": "jit_no_such_program"}}}) is None
