"""The benchmark's files for the latent family (one compressed row and one
roped key a token in a pool with no head axis and no values, read absorbed
or expanded; shared experts beside one routing group of the routed experts):
the configuration against its source, the mix and the cell against the
issue's numbers, the parameter count, the cost functions against hand counts,
a whole tiny run through the new runner, the readers, and the manifest's
accepted entries first and in order."""

import json
import os
import time

import pytest

import tiny_latent_moe as tiny
from perfbench import costs, costs_latent_moe, harness, run, traffic
from perfbench.layer_metrics import (
    expert_pairs_held_pct, latent_attend_share_pct)
from perfbench.references import latent_moe_lm

SEED = 2**31 + 41
CELL = "dsv2_code_backlog"


def _dsv2():
    with open(os.path.join(harness.ROOT, "perfbench/configs",
                           "deepseek-v2-6l.json")) as f:
        return json.load(f)


DSV2 = _dsv2()

# deepseek-ai/DeepSeek-V2 config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400}


def test_the_file_holds_the_published_numbers():
    differ = {k for k, v in PUBLISHED.items() if DSV2.get(k, "absent") != v}
    assert differ == set(DSV2["reduced"]) == {
        "num_hidden_layers", "n_routed_experts"}
    assert DSV2["num_hidden_layers"] == 6 and DSV2["n_routed_experts"] == 20
    assert DSV2["expert_share"] == {
        "published": 160, "first": 40, "chips_a_layer": 8, "this_chip": 2}
    # one routing group a chip: chip 2 holds group 2, experts 40-59
    assert DSV2["expert_share"]["first"] == 2 * (160 // DSV2["n_group"])
    assert DSV2["n_routed_experts"] == 160 // DSV2["n_group"]
    assert set(DSV2["reduced_why"]) == set(DSV2["reduced"])
    assert DSV2["source"].endswith(
        "deepseek-ai/DeepSeek-V2/blob/main/config.json")
    for key in ("model_code", "layer", "attention", "rope", "router",
                "experts", "weights", "kv_pool"):
        assert len(DSV2["assumed"][key]) > 40       # each with its reason
    assert "modeling_deepseek.py" in DSV2["assumed"]["model_code"]
    assert "1,152 bytes a token a layer" in DSV2["assumed"]["kv_pool"]
    assert "10 pipeline stages" in DSV2["deployment"]
    assert "80 TPU v5e chips" in DSV2["deployment"]
    assert "8 x these tokens" in DSV2["deployment"]
    assert "77 pairs a chunk" in DSV2["deployment"]
    serving = dict(DSV2["serving"])
    assert serving.pop("pool_reserve_bytes") > 0
    assert serving.pop("chunk_every") in (1, 2, 4)      # PERF.md's sweep
    # the issue's, number for number
    assert serving == {
        "max_batch": 32, "max_len": 18432, "block_size": 64,
        "paged_kernel": "xla", "prefill_chunk": 2048, "eos_id": -1,
        "admit_window_ms": 0.5}
    assert DSV2["kernels"] == {
        "decode_step": "jit_latent_moe_decode_step",
        "prefill": "jit_latent_moe_prefill_chunk"}
    assert (DSV2["runner"], DSV2["reference"], DSV2["dtype"]) == (
        "latent_moe_llm", "latent_moe_lm", "bfloat16")
    assert DSV2["check"]["sample_requests"] == 3
    assert list(DSV2["check"]["limits"]) == ["served_token_gap_mean"]
    assert DSV2["check"]["controls"] == ["fp8"]


def test_dims():
    m = latent_moe_lm.dims(DSV2)
    assert (m["d"], m["h"], m["rq"], m["rkv"]) == (5120, 128, 1536, 512)
    assert (m["nope"], m["rope"], m["v"]) == (128, 64, 128)
    assert (m["e"], m["first"], m["held"], m["k"]) == (160, 40, 20, 6)
    assert (m["groups"], m["topk_group"], m["renorm"]) == (8, 3, False)
    assert (m["f_dense"], m["f"], m["fs"]) == (12288, 1536, 3072)
    assert m["scale"] == 16.0 and m["dense"] == 1 and m["layers"] == 6
    assert m["yarn"] == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    # 77 pairs a chunk and held expert here, 614 in the deployment
    assert round(2048 * 6 / 160) == 77 and round(8 * 2048 * 6 / 160) == 614


def test_parameter_count():
    """The issue's arithmetic of the cut."""
    n = latent_moe_lm.param_count(DSV2)
    assert n == {"attention": 149225472, "dense_mlp": 188743680,
                 "shared": 47185920, "router": 819200, "expert": 23592960,
                 "outside": 1048576000}
    # Wqa 7.86, Wqb 37.75, Wkva 2.95, Wkvb 16.78, Wo 83.89 M
    assert n["attention"] == (5120 * 1536 + 1536 * 24576 + 5120 * 576
                              + 512 * 32768 + 16384 * 5120)
    outside = n["attention"] + n["shared"] + n["router"]
    assert round(outside / 1e6, 2) == 197.23
    expert_layer, dense_layer = outside + 20 * n["expert"], \
        n["attention"] + n["dense_mlp"]
    assert round(expert_layer / 1e6, 1) == 669.1
    assert round(dense_layer / 1e6, 2) == 337.97
    total = dense_layer + 5 * expert_layer + n["outside"]
    assert round(total / 1e6) == 4732
    assert 9.46e9 < 2 * total < 9.47e9              # bytes in bfloat16
    # a whole expert layer: one chip holds two and nothing else
    whole = outside + 160 * n["expert"]
    assert 7.93e9 < 2 * whole < 7.95e9
    assert costs_latent_moe.expert_bytes(DSV2) == 47185920


def test_pool_bytes():
    # one latent of 512 and one roped key of 64 in bfloat16, a layer
    assert costs_latent_moe.kv_bytes_per_token(DSV2, 2) == 6 * 1152 == 6912
    # 128 heads of K 192 and V 128 would be 81,920: 71 x
    assert 128 * (192 + 128) * 2 == 81920 and 81920 // 1152 == 71
    # the program lays its pool out to the same bytes, with no V pool
    import jax.numpy as jnp

    from nnstreamer_tpu.llm.paged_cache import PagedKVCache

    c = PagedKVCache(num_blocks=4, block_size=64, n_layers=6, n_kv=1,
                     head_dim=512, idx_dim=64, dtype=jnp.bfloat16,
                     values=False)
    assert c.block_bytes == 64 * 6912 and c.v is None
    assert c.block_bytes // (6 * 64) <= 1280
    # 32 rows of this traffic's mean context hold about 250 k tokens
    mix = traffic.load("code_backlog")
    mean = sum(p + o / 2 for p, o in mix["items"]) / 8
    assert 220e3 < 32 * mean < 260e3


M = latent_moe_lm.dims(DSV2)
MATS = 6 * 149225472 + 188743680 + 5 * (47185920 + 819200)


def test_attention_is_counted_in_the_cheaper_form():
    absorbed, expanded, expand = 2 * 128 * 1088, 2 * 128 * 320, 2 * 512 * 32768
    assert (absorbed, expanded, expand) == (278528, 81920, 33554432)
    # a decode step: one query a row, absorbed; nothing is expanded
    assert costs_latent_moe.attend_ops(M, 7000, 1, 7000) == absorbed * 7000
    # 2,048 queries behind 4,096 of context: expanded, the context's
    # keys through Wkvb once more (the chunk's own are among the matrices)
    pairs = 2048 * 4096 + 2048 * 2049 / 2
    assert costs_latent_moe.attend_ops(M, pairs, 2048, 6144) \
        == expanded * pairs + expand * 4096
    # few queries behind a long context: absorbed again
    pairs = 64 * 16000 + 64 * 65 / 2
    assert costs_latent_moe.attend_ops(M, pairs, 64, 16064) \
        == absorbed * pairs
    # the two cross near 170 queries a key
    for q, form in ((170, absorbed), (172, expanded)):
        pairs = q * 100000.0
        assert costs_latent_moe.attend_ops(M, pairs, q, 100000 + q) \
            == pytest.approx(form * pairs + (form == expanded) * expand
                             * 100000)


def test_decode_step_hand_count():
    ops, nbytes = costs_latent_moe.decode_step(
        DSV2, rows=32, kv_tokens=224000, experts_touched=70, pairs_held=120)
    mats = MATS + 5120 * 102400
    assert ops == (2.0 * 32 * mats + 2.0 * 120 * 3 * 5120 * 1536
                   + 6 * 278528.0 * 224000)
    assert nbytes == (2 * mats + 70 * 47185920 + 2 * 32 * 5120
                      + 1152 * 6 * (224000 + 32) + 4 * 32 * 102400)
    peaks = costs.peaks_for("TPU v5 lite")
    t, bound = costs.floor_seconds(ops, nbytes, peaks)
    # 1.79 + 0.47 + 0.38 + 1.05 GB of matrices, 3.3 of experts, 1.55 of latents
    assert bound == "memory" and 0.0100 < t < 0.0108
    assert 3.6e9 < 2 * mats < 3.8e9
    a_ops, a_bytes = costs_latent_moe.decode_attention(DSV2, 32, 224000)
    assert a_ops == 6 * 278528.0 * 224000 and 1.54e9 < a_bytes < 1.56e9
    # 242 operations a byte of latent: the v5e's ridge is 240
    assert 241 < a_ops / a_bytes < 243
    assert 239 < peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"] < 242


def test_prefill_chunk_hand_count():
    ops, nbytes = costs_latent_moe.prefill_chunk(
        DSV2, clen=2048, pos0=14336, experts_touched=100, pairs_held=7680)
    pairs = 2048 * 14336 + 2048 * 2049 / 2
    assert ops == (2.0 * 2048 * MATS + 2.0 * 5120 * 102400
                   + 2.0 * 7680 * 3 * 5120 * 1536
                   + 6 * (81920 * pairs + 33554432.0 * 14336))
    assert nbytes == (2 * (MATS + 5120 * 102400) + 100 * 47185920
                      + 2 * 2048 * 5120 + 1152 * 6 * 16384 + 4 * 102400)
    peaks = costs.peaks_for("TPU v5 lite")
    t, bound = costs.floor_seconds(ops, nbytes, peaks)
    assert bound == "compute" and 0.115 < t < 0.130         # 130 ms at 16 k
    first, _ = costs.floor_seconds(*costs_latent_moe.prefill_chunk(
        DSV2, 2048, 0, 100, 7680), peaks)
    assert 0.030 < first < 0.040
    # a whole prompt with nothing behind it: expanded is the cheaper form
    # however short, its own keys' expansion being among the matrices
    short, _ = costs_latent_moe.prefill_chunk(DSV2, 100, 0, 0, 0)
    assert short == (2.0 * 100 * MATS + 2.0 * 5120 * 102400
                     + 6 * 81920 * (100 * 101 / 2))
    # 6.13 us a context token of expansion and scores at 2,048 queries
    per_key = 6 * (81920 * 2048 + 33554432) / peaks["bf16_flops_per_s"]
    assert 6.1e-6 < per_key < 6.2e-6


def test_the_mix_and_the_cell():
    """The issue's items, arrival and rows, number for number."""
    mix = traffic.load("code_backlog")
    assert mix["items"] == [[1024, 512], [2048, 1024], [3072, 768],
                            [4096, 1024], [6144, 768], [8192, 1024],
                            [12288, 512], [16384, 1024]]
    assert mix["arrival"] == {"mode": "backlog", "ramp_s": 10.0, "base": 32,
                              "per_second": 2.0}
    offered = traffic.offered_work(mix, traffic.schedule(mix, SEED, 51.0))
    assert offered["n"] == 160                  # 20 whole multisets
    assert offered["prompt_tokens"] == 1064960
    assert offered["output_tokens"] == 133120
    assert max(p + o for p, o in offered["pairs"]) <= 18432 - 1024
    m = harness.load_manifest()
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == dict(cell, config="deepseek-v2-6l",
                        traffic="code_backlog", chips=1)
    entry = {c["name"]: c for c in m["configs"]}["deepseek-v2-6l"]
    assert entry["file"] == "perfbench/configs/deepseek-v2-6l.json"
    assert entry["reduced"] == DSV2["reduced"]
    assert entry["source"] == DSV2["source"]
    listed = [e["name"] for e in m["end_to_end"] + m["per_layer"]
              if CELL in e.get("workloads", ())]
    assert len(listed) == 10 and "tokens_per_s" in listed
    assert {"decode_step_roofline.tokens", "prefill_roofline.tokens",
            "kv_live_gb.tokens", "device_idle_pct.tokens"} <= set(listed)
    assert len(m["per_layer"]) == 14                # no entry added
    assert all(w["chips"] == 1 for w in m["workloads"])
    resolved = harness.resolve_cell(m, CELL)
    assert resolved.config == DSV2 and resolved.traffic == mix
    assert len(resolved.per_layer) == 9 and len(resolved.end_to_end) == 2


ACCEPTED_CELLS = ["ouro_chat_backlog", "keye_longctx_backlog",
                  "ouro_reason_backlog", "sala_longdoc_backlog",
                  "trinity_mixed_backlog", CELL]
ACCEPTED_CONFIGS = ["ouro-2.6b-1pass", "keye-vl-2.0-30b-a3b-6l",
                    "minicpm-sala-8l", "trinity-large-preview-5l",
                    "deepseek-v2-6l"]


def test_accepted_entries_come_first_and_in_order():
    """What a PR may do to the manifest: new entries at the end of their
    lists, the accepted ones before them in their order. In prefix form,
    so that the next cell's test needs no strict xfail here: a later PR
    appends its names to the two lists above, or leaves this test be.
    Also every assert of ``test_pb_window_moe.test_the_mix_and_the_cell``
    and ``::test_accepted_cells_stand_as_they_were_and_the_new_one_is_last``
    but their pins of Trinity's entries as the *last* ones, which no later
    cell can keep (``tests/conftest.py`` ``STALE_PINS``)."""
    m = harness.load_manifest()
    n = len(ACCEPTED_CELLS)
    assert [w["name"] for w in m["workloads"]][:n] == ACCEPTED_CELLS
    assert [c["name"] for c in m["configs"]][:n - 1] == ACCEPTED_CONFIGS
    lists = [e["workloads"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", ())]
    assert len(lists) == 10
    assert all(ws[:n] == ACCEPTED_CELLS for ws in lists)
    assert all(w["chips"] == 1 for w in m["workloads"][:n])
    assert len(m["per_layer"]) >= 14
    # Trinity's mix and cell, as test_pb_window_moe holds them
    mix = traffic.load("mixed_backlog")
    assert mix["items"] == [[1024, 256], [2048, 384], [3072, 256],
                            [4096, 512], [16384, 384], [24576, 256],
                            [32768, 512], [49152, 384]]
    assert mix["arrival"] == {"mode": "backlog", "ramp_s": 10.0, "base": 32,
                              "per_second": 2.5}
    offered = traffic.offered_work(mix, traffic.schedule(mix, SEED, 51.0))
    assert offered["n"] == 192
    assert offered["prompt_tokens"] == 3194880
    assert offered["output_tokens"] == 70656
    assert max(p + o for p, o in offered["pairs"]) <= 51200
    assert sum(p > 4096 for p, _ in mix["items"]) == 4
    cells = {w["name"]: w for w in m["workloads"]}
    trinity = cells["trinity_mixed_backlog"]
    assert trinity == dict(trinity, config="trinity-large-preview-5l",
                           traffic="mixed_backlog", chips=1)
    listed = [e["name"] for e in m["end_to_end"] + m["per_layer"]
              if "trinity_mixed_backlog" in e.get("workloads", ())]
    assert len(listed) == 10 and "tokens_per_s" in listed
    assert {"decode_step_roofline.tokens", "prefill_roofline.tokens",
            "kv_live_gb.tokens", "device_idle_pct.tokens"} <= set(listed)
    # SALA's, as both tests before this one held them
    sala = cells["sala_longdoc_backlog"]
    assert sala == dict(sala, config="minicpm-sala-8l",
                        traffic="longdoc_backlog", chips=1)
    mix = traffic.load("longdoc_backlog")
    assert mix["items"] == [[8192, 1024], [12288, 512], [16384, 768],
                            [24576, 1024], [32768, 512], [32768, 768],
                            [49152, 1024], [65536, 512]]
    assert mix["arrival"] == {"mode": "backlog", "ramp_s": 3.0, "base": 32,
                              "per_second": 1.0}
    offered = traffic.offered_work(mix, traffic.schedule(mix, SEED, 51.0))
    assert offered["n"] == 88 and offered["output_tokens"] == 67584
    assert offered["prompt_tokens"] / 88 == 30208
    assert max(p + o for p, o in offered["pairs"]) <= 66560


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


def test_trace_run_reports_the_pool_through_the_readers():
    cell = tiny.cell()
    names = ["kv_live_gb.tokens", "expert_pairs_held_pct.tokens",
             "decode_batch_avg.tokens", "admission_blocked.tokens",
             "latent_attend_share_pct.tokens", "decode_step_roofline.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    got = _run(trace=True, cell=cell)["metrics"]
    # blocks as the program counts them: a latent of 16 and a roped key of
    # 4 of four tokens, three layers, float32
    block = 3 * 4 * (16 + 4) * 4
    held = got["kv_live_gb.tokens"]["value"] * 1e9 / block
    assert held == pytest.approx(round(held)) and 1 <= held <= 4 * 16
    # 2 of 16 experts held: about an eighth of the pairs, as the router deals
    assert 2.0 < got["expert_pairs_held_pct.tokens"]["value"] < 40.0
    assert got["decode_batch_avg.tokens"]["value"] >= 1.0
    # shares of a chip's floors are read on that chip only
    assert "latent_attend_share_pct.tokens" not in got
    assert "decode_step_roofline.tokens" not in got     # no TPU plane


def test_a_program_without_the_family_fails_cleanly(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    `llm/spec.py` has no such family, and the runner says so before any
    weight is made."""
    from nnstreamer_tpu.llm import spec
    from perfbench.runners import latent_moe_llm

    monkeypatch.delattr(spec, "LATENT_MOE")
    with pytest.raises(harness.HarnessError, match="no latent family"):
        latent_moe_llm.Runner(tiny.cell(), SEED, 1.0, False, [])


def test_readers_on_planted_readings():
    ctx = {"counters": {"start": {"expert_pairs_held": 100,
                                  "expert_pairs_away": 700},
                        "end": {"expert_pairs_held": 1100,
                                "expert_pairs_away": 7700}}}
    assert expert_pairs_held_pct.read(ctx) == pytest.approx(12.5)
    peaks = costs.peaks_for("TPU v5 lite")
    flops, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    # a step bound by its bytes whose attention is bound by its operations,
    # and a chunk bound by its operations: floors of 2 + 8 ms, 1 + 2 of
    # them the attention's
    ctx = {"device_kind": "TPU v5 lite",
           "kernel_calls": {"decode_step": [(1.0, 0.002 * bw)],
                            "prefill": [(0.008 * flops, 1.0)]},
           "attend_calls": {"decode_step": [(0.001 * flops, 1.0)],
                            "prefill": [(0.002 * flops, 1.0)]}}
    assert latent_attend_share_pct.read(ctx) == pytest.approx(30.0)
    # a program that says nothing of its attention: nothing to read
    assert latent_attend_share_pct.read(
        {"device_kind": "TPU v5 lite", "kernel_calls": {}}) is None
    assert latent_attend_share_pct.read(
        dict(ctx, attend_calls={"decode_step": []})) is None
    assert latent_attend_share_pct.read(dict(ctx, device_kind="cpu")) is None
