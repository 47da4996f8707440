"""The benchmark's files for the delta family (layers of gated delta-rule
linear attention, a float32 state and three convolutions' tails a sequence
by slot, beside latent layers without rope over a pool by block; a shared
expert beside one chip's share of the routed experts): the configuration
against its source, the mix and the cell against the issue's numbers, the
parameter count, the cost functions against hand counts, a whole tiny run
through the new runner, the readers, and the manifest's accepted entries
first and in order."""

import json
import os
import time

import pytest

import tiny_delta_moe as tiny
from perfbench import costs, costs_delta_moe, harness, run, traffic
from perfbench.layer_metrics import (
    delta_share_pct, expert_pairs_held_pct, latent_attend_share_pct,
    state_live_gb, state_share_of_step_pct)
from perfbench.references import delta_moe_lm

SEED = 2**31 + 45
CELL = "kimi_reason_backlog"
NAME = "kimi-linear-48b-a3b-8l"


def _kimi():
    with open(os.path.join(harness.ROOT, "perfbench/configs",
                           NAME + ".json")) as f:
        return json.load(f)


KIMI = _kimi()

# moonshotai/Kimi-Linear-48B-A3B-Instruct config.json, as the catalog has it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_the_file_holds_the_published_numbers():
    """Every key of the source under the same key, changed only where
    `reduced` says so; no width among the reduced."""
    reduced = KIMI["reduced"]
    assert reduced == ["num_hidden_layers", "num_experts",
                       "linear_attn_config"]
    assert set(KIMI["reduced_why"]) == set(reduced)
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert KIMI[key] == value, key
    assert KIMI["num_hidden_layers"] == 8 and KIMI["num_experts"] == 64
    lin, pub = KIMI["linear_attn_config"], PUBLISHED["linear_attn_config"]
    # the two lists cut to the layers 1-8, the sizes inside as published
    assert lin["kda_layers"] == [n for n in pub["kda_layers"] if n <= 8]
    assert lin["full_attn_layers"] == [4, 8]
    assert {k: lin[k] for k in ("head_dim", "num_heads",
                                "short_conv_kernel_size")} \
        == {k: pub[k] for k in ("head_dim", "num_heads",
                                "short_conv_kernel_size")}
    assert KIMI["source"].startswith(
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/")
    assert KIMI["expert_share"] == {"published": 256, "first": 0,
                                    "chips_a_layer": 4, "this_chip": 0}
    assert {"model_code", "layer", "kda", "latent", "router", "experts",
            "weights", "caches"} <= set(KIMI["assumed"])
    assert "16 TPU v5e chips" in KIMI["deployment"]
    assert "not the deployment's" in KIMI["deployment"]
    s = KIMI["serving"]
    assert (s["max_batch"], s["max_len"], s["block_size"],
            s["prefill_chunk"], s["chunk_every"], s["eos_id"]) \
        == (64, 18432, 64, 2048, 2, -1)
    assert KIMI["dtype"] == "bfloat16"
    assert KIMI["kernels"] == {"decode_step": "jit_delta_moe_decode_step",
                               "prefill": "jit_delta_moe_prefill_chunk"}
    assert KIMI["check"]["sample_requests"] == 3
    assert list(KIMI["check"]["limits"]) == ["served_token_gap_mean"]
    assert KIMI["check"]["controls"] == ["fp8"]


def test_dims():
    m = delta_moe_lm.dims(KIMI)
    assert (m["d"], m["h"], m["kh"], m["kd"], m["conv"], m["r"]) \
        == (2304, 32, 32, 128, 4, 128)
    assert (m["rkv"], m["nope"], m["rope"], m["v"]) == (512, 128, 64, 128)
    assert (m["f_dense"], m["f"], m["fs"]) == (9216, 1024, 1024)
    assert (m["e"], m["first"], m["held"], m["k"]) == (256, 0, 64, 8)
    assert m["scale"] == 2.446 and m["dense"] == 1 and m["layers"] == 8
    assert m["kinds"] == ("kda",) * 3 + ("latent",) + ("kda",) * 3 \
        + ("latent",)
    assert m["vocab"] == 163840 and m["eps"] == 1e-5
    # a decode step of 64 rows gives a held expert 2 pairs, a chunk 64
    assert 64 * 8 / 256 == 2 and 2048 * 8 / 256 == 64
    with pytest.raises(ValueError, match="names each layer once"):
        delta_moe_lm.dims(dict(KIMI, num_hidden_layers=9))


def test_parameter_count():
    """The sums of the file's `reduced_why`, redone."""
    n = delta_moe_lm.param_count(KIMI)
    assert n["kda"] == 3 * 2304 * 4096 + 4096 * 2304 \
        + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4096 * 4
    assert round(n["kda"] / 1e6, 2) == 39.51
    assert round(n["latent"] / 1e6, 2) == 29.11
    assert round(n["dense_mlp"] / 1e6, 2) == 63.70
    assert round((n["shared"] + n["router"]) / 1e6, 2) == 7.67
    assert round(n["expert"] / 1e6, 3) == 7.078
    assert round(64 * n["expert"] / 1e6, 2) == 452.98
    assert round(n["outside"] / 1e6, 2) == 754.97
    total = delta_moe_lm.total_params(KIMI)
    assert total == 6 * n["kda"] + 2 * n["latent"] + n["dense_mlp"] \
        + 7 * (n["shared"] + n["router"] + 64 * n["expert"]) + n["outside"]
    assert round(total / 1e6) == 4339
    assert round(2 * total / 1e9, 2) == 8.68
    # the weights as made are the count (vectors apart)
    import jax
    small = delta_moe_lm.make_params(tiny.CONFIG, SEED)
    mats = sum(int(x.size) for x in jax.tree_util.tree_leaves(small)
               if x.ndim >= 2)
    assert mats == delta_moe_lm.total_params(tiny.CONFIG)


def test_cache_bytes():
    """A row's slot and a token's block, in the program's own bytes."""
    assert costs_delta_moe.state_bytes_per_seq(KIMI) == 12582912
    assert costs_delta_moe.tail_bytes_per_seq(KIMI, 2) == 442368
    assert costs_delta_moe.slot_bytes_per_seq(KIMI) == 13025280
    assert costs_delta_moe.kv_bytes_per_token(KIMI, 2) == 2 * 1152 == 2304
    assert costs_delta_moe.expert_bytes(KIMI) == 2 * 3 * 2304 * 1024
    from nnstreamer_tpu.llm.paged_cache import PagedKVCache
    import jax.numpy as jnp
    c = PagedKVCache(num_blocks=4, block_size=64, n_layers=2, n_kv=1,
                     head_dim=512, idx_dim=64, dtype=jnp.bfloat16,
                     values=False, state_slots=1,
                     state_shape=(6, 32, 128, 128),
                     row_shape=(6, 1, 9 * 4096))
    assert c.block_bytes == 64 * 2304
    assert c.state_slot_bytes == costs_delta_moe.slot_bytes_per_seq(KIMI)
    # 65 slots, as the engine asks for 64 rows: 0.85 GB
    assert round(65 * c.state_slot_bytes / 1e9, 2) == 0.85


def test_decode_step_hand_count():
    """64 rows, 320,000 cached positions, 385 experts touched (55 of 64 a
    layer), 3,584 pairs held (a quarter of 64 x 8 x 7)."""
    ops, nbytes = costs_delta_moe.decode_step(KIMI, 64, 320000, 385, 3584)
    n = delta_moe_lm.param_count(KIMI)
    outside = 6 * n["kda"] + 2 * n["latent"] + n["dense_mlp"] \
        + 7 * (n["shared"] + n["router"])
    assert round(outside / 1e6, 2) == 412.66
    head = 2304 * 163840
    d_ops, d_bytes = costs_delta_moe.decode_delta(KIMI, 64)
    assert d_bytes == 2 * 64 * 13025280                 # 1.67 GB a step
    assert d_ops == 6 * 64 * (7 * 32 * 128 * 128 + 2 * 4 * 3 * 4096)
    a_ops, a_bytes = costs_delta_moe.decode_attention(KIMI, 64, 320000)
    assert a_bytes == 2 * 1152 * (320000 + 64)
    assert a_ops == 2 * 2.0 * 32 * (2 * 512 + 64) * 320000  # absorbed
    assert nbytes == 2 * (outside + head) + 385 * 14155776 + 2 * 64 * 2304 \
        + a_bytes + d_bytes + 4 * 64 * 163840
    assert ops == 2.0 * 64 * (outside + head) + 2.0 * 3584 * 3 * 2304 * 1024 \
        + a_ops + d_ops
    # bound by its bytes: 10.2 GB, 12.5 ms at the chip's bandwidth
    peaks = costs.peaks_for("TPU v5 lite")
    floor, bound = costs.floor_seconds(ops, nbytes, peaks)
    assert bound == "memory" and 0.0115 < floor < 0.0135


def test_prefill_chunk_hand_count():
    ops, nbytes = costs_delta_moe.prefill_chunk(KIMI, 2048, 4096, 448, 28672)
    fresh = costs_delta_moe.chunk_delta(KIMI, 2048, True)
    later = costs_delta_moe.chunk_delta(KIMI, 2048, False)
    assert fresh[1] == 13025280 and later[1] == 2 * 13025280
    assert fresh[0] == later[0] == 6 * 2048 * (
        7 * 32 * 128 * 128 + 2 * 4 * 3 * 4096)
    a_ops, a_bytes = costs_delta_moe.chunk_attention(KIMI, 2048, 4096)
    pairs = 2048 * 4096 + 2048 * 2049 / 2
    expanded = 2.0 * 32 * 320 * pairs + 2.0 * 512 * 32 * 256 * 4096
    assert a_ops == 2 * expanded                # the cheaper form there
    assert a_bytes == 2 * 1152 * 6144
    n = delta_moe_lm.param_count(KIMI)
    outside = 6 * n["kda"] + 2 * n["latent"] + n["dense_mlp"] \
        + 7 * (n["shared"] + n["router"])
    assert ops == 2.0 * 2048 * outside + 2.0 * 2304 * 163840 + a_ops \
        + later[0] + 2.0 * 28672 * 3 * 2304 * 1024
    assert nbytes == 2 * (outside + 2304 * 163840) + 448 * 14155776 \
        + 2 * 2048 * 2304 + a_bytes + later[1] + 4 * 163840
    floor, bound = costs.floor_seconds(ops, nbytes,
                                       costs.peaks_for("TPU v5 lite"))
    assert bound == "compute" and 0.010 < floor < 0.016


def test_the_mix_and_the_cell():
    mix = traffic.load("think_backlog")
    assert mix["items"] == [[256, 1024], [512, 1536], [512, 2048],
                            [1024, 1024], [1024, 2048], [2048, 1536],
                            [4096, 1024], [16384, 1024]]
    assert mix["arrival"] == {"mode": "backlog", "ramp_s": 10.0, "base": 64,
                              "per_second": 2.0}
    offered = traffic.offered_work(mix, traffic.schedule(mix, SEED, 51.0))
    assert offered["n"] == 192                  # 24 whole multisets
    assert offered["prompt_tokens"] == 620544
    assert offered["output_tokens"] == 270336
    assert max(p + o for p, o in offered["pairs"]) <= 18432
    assert sum(p for p, _ in mix["items"]) / 8 == 3232
    assert sum(o for _, o in mix["items"]) / 8 == 1408
    m = harness.load_manifest()
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == dict(cell, config=NAME, traffic="think_backlog", chips=1)
    entry = {c["name"]: c for c in m["configs"]}[NAME]
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["reduced"] == KIMI["reduced"]
    assert entry["source"] == KIMI["source"]
    listed = [e["name"] for e in m["end_to_end"] + m["per_layer"]
              if CELL in e.get("workloads", ())]
    assert len(listed) == 9 and "tokens_per_s" in listed
    assert {"decode_step_roofline.tokens",
            "kv_live_gb.tokens", "device_idle_pct.tokens",
            "decode_batch_avg.tokens", "compiles_in_window.tokens",
            "admission_blocked.tokens", "gen_lag_p99_ms.tokens",
            "answer_stall_max_ms.tokens"} <= set(listed)
    # the traced stretch is the window's middle (22.5-28.5 s of 51): the
    # 64 rows are filled by then and the first answer (1,024 tokens at
    # 38 ms a step) ends after it, so no chunk falls there and the
    # chunk's roofline has nothing to read in this cell (PERF.md, PR 45)
    assert "prefill_roofline.tokens" not in listed
    assert len(m["per_layer"]) == 14                # no entry added
    resolved = harness.resolve_cell(m, CELL)
    assert resolved.config == KIMI and resolved.traffic == mix
    assert len(resolved.per_layer) == 8 and len(resolved.end_to_end) == 2


ACCEPTED_CELLS = ["ouro_chat_backlog", "keye_longctx_backlog",
                  "ouro_reason_backlog", "sala_longdoc_backlog",
                  "trinity_mixed_backlog", "dsv2_code_backlog", CELL]
ACCEPTED_CONFIGS = ["ouro-2.6b-1pass", "keye-vl-2.0-30b-a3b-6l",
                    "minicpm-sala-8l", "trinity-large-preview-5l",
                    "deepseek-v2-6l", NAME]


def test_accepted_entries_come_first_and_in_order():
    """The manifest in prefix form, with seven cells and six
    configurations: new entries at the end of their lists, the accepted
    ones before them in their order (a later PR appends its names to the
    two lists above, or leaves this test be)."""
    m = harness.load_manifest()
    n = len(ACCEPTED_CELLS)
    assert [w["name"] for w in m["workloads"]][:n] == ACCEPTED_CELLS
    assert [c["name"] for c in m["configs"]][:n - 1] == ACCEPTED_CONFIGS
    lists = [e["workloads"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", ())]
    assert len(lists) == 9
    assert all(ws[:n] == ACCEPTED_CELLS for ws in lists)
    assert all(w["chips"] == 1 for w in m["workloads"][:n])
    assert len(m["per_layer"]) >= 14
    assert [c["file"] for c in m["configs"]][:n - 1] == [
        f"perfbench/configs/{name}.json" for name in ACCEPTED_CONFIGS]
    # every configuration is some cell's, and every cell's file is there
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for w in m["workloads"][:n]:
        assert os.path.exists(os.path.join(
            harness.ROOT, "perfbench/mixes", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200


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


def test_a_state_kept_in_the_compute_type_is_not_correct(monkeypatch):
    """What a lower precision in the mechanism itself does to `correct`:
    the state rounded to bfloat16 after every decode step is a different
    result, and the tiny cell's limit sees it."""
    import jax.numpy as jnp

    from nnstreamer_tpu.llm import delta_moe

    sound = delta_moe.delta_step

    def rounded(q, k, v, g, beta, state):
        o, state = sound(q, k, v, g, beta, state)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(delta_moe, "delta_step", rounded)
    import jax
    jax.clear_caches()
    try:
        cell = tiny.cell()
        cell.config["check"]["sample_requests"] = 1000
        assert _run(cell=cell)["correct"] is False
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_trace_run_fills_every_reading_the_readers_take():
    """A tiny traced run of the runner off the chip: the counters and the
    readings the readers in place need, and the new reader's."""
    cell = tiny.cell()
    names = ["kv_live_gb.tokens", "state_live_gb.tokens",
             "state_share_of_step_pct.tokens", "expert_pairs_held_pct.tokens",
             "decode_batch_avg.tokens", "admission_blocked.tokens",
             "latent_attend_share_pct.tokens", "delta_share_pct.tokens",
             "decode_step_roofline.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    seen = {}
    real = harness.read_layer_metrics

    def spy(c, ctx):
        seen.update(ctx)
        return real(c, ctx)

    harness.read_layer_metrics = spy
    try:
        got = _run(trace=True, cell=cell)["metrics"]
    finally:
        harness.read_layer_metrics = real
    # blocks as the program counts them: a latent of 16 and a shared key
    # of 4 of four tokens, two latent layers, float32
    block = 2 * 4 * (16 + 4) * 4
    held = got["kv_live_gb.tokens"]["value"] * 1e9 / block
    assert held == pytest.approx(round(held)) and 1 <= held <= 4 * 16
    # a slot: 3 KDA layers' states and tails, float32
    slot = 3 * 2 * 8 * 8 * 4 + 3 * 3 * 48 * 4
    assert seen["state_slot_bytes"] == slot
    rows = got["state_live_gb.tokens"]["value"] * 1e9 / slot
    assert rows == pytest.approx(round(rows)) and 1 <= rows <= 4
    assert 0.0 < got["state_share_of_step_pct.tokens"]["value"] < 100.0
    assert 5.0 < got["expert_pairs_held_pct.tokens"]["value"] < 60.0
    assert got["decode_batch_avg.tokens"]["value"] >= 1.0
    # shares of a chip's floors are read on that chip only
    assert "latent_attend_share_pct.tokens" not in got
    assert "delta_share_pct.tokens" not in got
    assert "decode_step_roofline.tokens" not in got     # no TPU plane
    # what the readers would take there: a call's parts beside the call
    calls = seen["kernel_calls"]
    assert calls["decode_step"] and calls["prefill"]
    for part in ("attend_calls", "delta_calls"):
        assert {k: len(v) for k, v in seen[part].items()} \
            == {k: len(v) for k, v in calls.items()}
    assert len(seen["decode_state_bytes"]) == len(calls["decode_step"])
    assert seen["chunk_spans"] and {"clen", "pos0", "expert_load_max",
                                    "experts_touched"} <= set(
                                        seen["chunk_spans"][0])
    end = seen["counters"]["end"]
    for key in ("state_slots_used", "admission_blocked_state", "state_rows",
                "state_bytes_rw", "tail_bytes_rw", "delta_runs",
                "chunks_fresh", "expert_pairs_held", "expert_pairs_away",
                "kv_tokens_attended", "latents_expanded", "chunk_prefills",
                "decode_steps_plain"):
        assert key in end, key
    # on a chip whose peaks the benchmark knows, the new reader reads
    share = delta_share_pct.read(dict(seen, device_kind="TPU v5 lite"))
    assert 0.0 < share < 100.0
    assert 0.0 < latent_attend_share_pct.read(
        dict(seen, device_kind="TPU v5 lite")) < 100.0


def test_a_program_without_the_family_fails_cleanly(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    `llm/spec.py` has no such family, and the runner says so before any
    weight is made."""
    from nnstreamer_tpu.llm import spec
    from perfbench.runners import delta_moe_llm

    monkeypatch.delattr(spec, "DELTA_MOE")
    with pytest.raises(harness.HarnessError, match="no delta family"):
        delta_moe_llm.Runner(tiny.cell(), SEED, 1.0, False, [])
    # and a configuration this runner does not describe
    monkeypatch.undo()
    with pytest.raises(harness.HarnessError, match="no rank and no rope"):
        delta_moe_llm.lm_spec(dict(tiny.CONFIG, mla_use_nope=False))


def test_readers_on_planted_readings():
    ctx = {"counters": {"start": {"expert_pairs_held": 100,
                                  "expert_pairs_away": 300},
                        "end": {"expert_pairs_held": 1100,
                                "expert_pairs_away": 3300,
                                "state_slots_used": 64}},
           "state_slot_bytes": 13025280}
    assert expert_pairs_held_pct.read(ctx) == pytest.approx(25.0)
    assert state_live_gb.read(ctx) == pytest.approx(0.83361792)
    peaks = costs.peaks_for("TPU v5 lite")
    flops, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    # a step bound by its bytes, a fifth of them the states' and tails';
    # a chunk bound by its operations, a twentieth of them the rule's:
    # floors of 10 + 20 ms, 2 + 1 of them the delta layers'
    ctx = {"device_kind": "TPU v5 lite",
           "kernel_calls": {"decode_step": [(1.0, 0.010 * bw)],
                            "prefill": [(0.020 * flops, 1.0)]},
           "delta_calls": {"decode_step": [(1.0, 0.002 * bw)],
                           "prefill": [(0.001 * flops, 1.0)]},
           "decode_state_bytes": [0.0015 * bw]}
    assert delta_share_pct.read(ctx) == pytest.approx(10.0)
    assert state_share_of_step_pct.read(ctx) == pytest.approx(15.0)
    # a program that says nothing of its delta layers (the parent's, any
    # other family's): nothing to read, and nothing raised
    assert delta_share_pct.read(
        {"device_kind": "TPU v5 lite", "kernel_calls": {}}) is None
    assert delta_share_pct.read({"device_kind": "TPU v5 lite"}) is None
    assert delta_share_pct.read(
        dict(ctx, delta_calls={"decode_step": []})) is None
    assert delta_share_pct.read(dict(ctx, device_kind="cpu")) is None
