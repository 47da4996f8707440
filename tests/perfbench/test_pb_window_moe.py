"""The benchmark's files for the window family (layers of window and of
full attention over a pool a kind, a shared expert beside a share of the
routed experts): the configuration against its source, the mix and the cell
against the issue's numbers, the parameter count, the cost functions against
hand counts, a whole tiny run through the new runner, the two new readers."""

import json
import os
import time

import pytest

import tiny_window_moe as tiny
from perfbench import costs, costs_window_moe, harness, run, traffic
from perfbench.layer_metrics import expert_pairs_held_pct, kv_window_live_gb
from perfbench.references import window_moe_lm

SEED = 2**31 + 39
CELL = "trinity_mixed_backlog"


def _trinity():
    with open(os.path.join(harness.ROOT, "perfbench/configs",
                           "trinity-large-preview-5l.json")) as f:
        return json.load(f)


TRINITY = _trinity()

S, F = "sliding_attention", "full_attention"

# arcee-ai/Trinity-Large-Preview config.json, as the catalog has it
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": [S, S, S, F] * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_trinity_file_holds_the_published_numbers():
    differ = {k for k, v in PUBLISHED.items()
              if TRINITY.get(k, "absent") != v}
    assert differ == set(TRINITY["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts"}
    assert TRINITY["num_hidden_layers"] == 5
    assert TRINITY["num_dense_layers"] == 1 and TRINITY["num_experts"] == 32
    # the published layers 5-9: the last dense layer, then one whole
    # period of the expert layers, three window to one full
    assert TRINITY["layer_types"] == PUBLISHED["layer_types"][5:10] \
        == [S, S, F, S, S]
    assert TRINITY["layer_types"][1:].count(S) == 3
    assert TRINITY["expert_share"] == {
        "published": 256, "first": 64, "chips_a_layer": 8, "this_chip": 2}
    assert TRINITY["expert_share"]["first"] == 2 * 32
    assert set(TRINITY["reduced_why"]) == set(TRINITY["reduced"])
    assert TRINITY["source"].endswith(
        "arcee-ai/Trinity-Large-Preview/blob/main/config.json")
    for key in ("model_code", "layer", "embedding", "attention", "router",
                "experts", "weights"):
        assert len(TRINITY["assumed"][key]) > 40    # each with its reason
    assert "12 pipeline stages" in TRINITY["deployment"]
    assert "8 x these tokens" in TRINITY["deployment"]
    serving = dict(TRINITY["serving"])
    assert serving.pop("pool_reserve_bytes") > 0
    assert serving.pop("chunk_every") in (1, 2, 3, 4)   # PERF.md's sweep
    assert serving == {
        "max_batch": 16, "max_len": 51200, "block_size": 64,
        "paged_kernel": "xla", "prefill_chunk": 2048, "eos_id": -1,
        "admit_window_ms": 0.5}
    assert TRINITY["kernels"] == {
        "decode_step": "jit_window_moe_decode_step",
        "prefill": "jit_window_moe_prefill_chunk"}
    assert TRINITY["check"]["sample_requests"] == 3
    assert list(TRINITY["check"]["limits"]) == ["served_token_gap_mean"]
    assert TRINITY["check"]["controls"] == ["fp8"]


def test_trinity_dims():
    m = window_moe_lm.dims(TRINITY)
    assert (m["d"], m["h"], m["hkv"], m["hd"]) == (3072, 48, 8, 128)
    assert (m["e"], m["first"], m["held"], m["k"]) == (256, 64, 32, 4)
    assert (m["f_dense"], m["f"], m["fs"]) == (12288, 3072, 3072)
    assert m["window"] == 4096 and m["scale"] == 2.448 and m["dense"] == 1
    assert m["emb_scale"] == pytest.approx(3072 ** 0.5)


def test_trinity_parameter_count():
    """The issue's arithmetic of the cut."""
    n = window_moe_lm.param_count(TRINITY)
    assert n == {"attention": 62914560, "dense_mlp": 113246208,
                 "shared": 28311552, "router": 786432, "expert": 28311552,
                 "outside": 1229979648}
    expert_layer = (n["attention"] + n["shared"] + n["router"]
                    + 32 * n["expert"])
    dense_layer = n["attention"] + n["dense_mlp"]
    assert round(expert_layer / 1e6, 1) == 998.0
    assert round(dense_layer / 1e6, 1) == 176.2
    total = 4 * expert_layer + dense_layer + n["outside"]
    assert 10.79e9 < 2 * total < 10.81e9            # bytes in bfloat16
    # a whole expert layer does not fit one chip beside anything else
    whole = n["attention"] + n["shared"] + n["router"] + 256 * n["expert"]
    assert 14.6e9 < 2 * whole < 14.8e9


def test_pool_bytes():
    # K and V of 8 heads of 128 in bfloat16, a layer: 4,096 bytes a token
    assert costs_window_moe.kv_bytes_per_token(TRINITY, 2, "full") == 4096
    assert costs_window_moe.kv_bytes_per_token(TRINITY, 2, "window") == 16384
    assert costs_window_moe.window_cap(TRINITY, 1, 64) == 65
    assert costs_window_moe.window_cap(TRINITY, 2048, 64) == 97
    # 16 rows x 4,160 tokens + one chunk (and the scratch block): 1.12 GB
    blocks = costs_window_moe.window_pool_blocks(TRINITY, 16, 2048, 64)
    assert blocks == 16 * 65 + 32 + 1
    assert (blocks - 1) * 64 == 16 * 4160 + 2048
    assert 1.12e9 < blocks * 64 * 16384 < 1.13e9
    # the program sizes its window pools by the same rule
    from nnstreamer_tpu.llm.paged_cache import window_cap

    assert window_cap(4096, 64, 2048) == 97 and window_cap(4096, 64, 1) == 65
    # a row of 32 k tokens: 671 MB under one table, 201 MB under two
    assert 32768 * 4096 * 5 == 671088640
    assert round((32768 * 4096 + 4 * 4096 * 4096) / 1e6) == 201


MATS = 5 * 62914560 + 113246208 + 4 * (28311552 + 786432)


def test_decode_step_hand_count():
    ops, nbytes = costs_window_moe.decode_step(
        TRINITY, rows=16, kv_full=286000, kv_window=16 * 4096,
        experts_touched=28, pairs_held=32)
    mats = MATS + 3072 * 200192
    attended = 286000 + 4 * 16 * 4096
    assert ops == (2.0 * 16 * mats + 2.0 * 32 * 3 * 3072 * 3072
                   + 4.0 * attended * 48 * 128)
    assert nbytes == (2 * mats + 28 * 56623104 + 2 * 16 * 3072
                      + 4096 * attended + 4096 * 16 * 5 + 4 * 16 * 200192)
    t, bound = costs.floor_seconds(ops, nbytes,
                                   costs.peaks_for("TPU v5 lite"))
    # 2.3 GB outside the routed experts, 1.6 GB of experts, 2.2 GB of context
    assert bound == "memory" and 0.0070 < t < 0.0078
    assert 2.2e9 < 2 * mats < 2.4e9


def test_prefill_chunk_hand_count():
    ops, nbytes = costs_window_moe.prefill_chunk(
        TRINITY, clen=2048, pos0=14336, experts_touched=128,
        pairs_held=4096)
    full = 2048 * 14336 + 2048 * 2049 / 2
    assert ops == (2.0 * 2048 * MATS + 2.0 * 3072 * 200192
                   + 2.0 * 4096 * 3 * 3072 * 3072
                   + 4.0 * (full + 4 * 2048 * 4096) * 48 * 128)
    assert nbytes == (2 * (MATS + 3072 * 200192) + 128 * 56623104
                      + 2 * 2048 * 3072
                      + 4096 * (16384 + 4 * (4095 + 2048)) + 4 * 200192)
    t, bound = costs.floor_seconds(ops, nbytes,
                                   costs.peaks_for("TPU v5 lite"))
    assert bound == "compute" and 0.018 < t < 0.028
    # a short whole prompt inside the window: both kinds attend alike
    short, _ = costs_window_moe.prefill_chunk(TRINITY, 100, 0, 0, 0)
    assert short == (2.0 * 100 * MATS + 2.0 * 3072 * 200192
                     + 4.0 * 5 * (100 * 101 / 2) * 48 * 128)


def test_the_mix_and_the_cell():
    """The issue's items, arrival and rows, number for number."""
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
    # half the requests cross the window
    assert sum(p > 4096 for p, _ in mix["items"]) == 4
    m = harness.load_manifest()
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == dict(cell, config="trinity-large-preview-5l",
                        traffic="mixed_backlog", chips=1)
    assert len(m["workloads"]) == 5 and len(m["configs"]) == 4
    assert all(w["chips"] == 1 for w in m["workloads"])
    listed = [e["name"] for e in m["end_to_end"] + m["per_layer"]
              if CELL in e.get("workloads", ())]
    assert len(listed) == 10 and "tokens_per_s" in listed
    assert {"decode_step_roofline.tokens", "prefill_roofline.tokens",
            "kv_live_gb.tokens", "device_idle_pct.tokens"} <= set(listed)
    assert len(m["per_layer"]) == 14                # no entry added


def test_accepted_cells_stand_as_they_were_and_the_new_one_is_last():
    """What a PR may do to the manifest: new entries at the end of their
    lists, the accepted ones before them in their order.  Also every
    assert of ``test_pb_hybrid.test_the_mix_and_the_cell`` but its pins
    of SALA's entries as the *last* ones, which no later cell can keep
    (``tests/conftest.py`` ``STALE_PINS``)."""
    sala = "sala_longdoc_backlog"
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
    m = harness.load_manifest()
    cell = {w["name"]: w for w in m["workloads"]}[sala]
    assert cell == dict(cell, config="minicpm-sala-8l",
                        traffic="longdoc_backlog", chips=1)
    assert [w["name"] for w in m["workloads"]] == [
        "ouro_chat_backlog", "keye_longctx_backlog", "ouro_reason_backlog",
        sala, CELL]
    assert [c["name"] for c in m["configs"]] == [
        "ouro-2.6b-1pass", "keye-vl-2.0-30b-a3b-6l", "minicpm-sala-8l",
        "trinity-large-preview-5l"]
    lists = [e["workloads"] for e in m["end_to_end"] + m["per_layer"]
             if sala in e.get("workloads", ())]
    assert len(lists) == 10
    assert all(ws[-2:] == [sala, CELL] for ws in lists)


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


def test_trace_run_reports_both_pools_through_the_readers():
    cell = tiny.cell()
    names = ["kv_window_live_gb.tokens", "expert_pairs_held_pct.tokens",
             "kv_live_gb.tokens", "decode_batch_avg.tokens",
             "admission_blocked.tokens", "decode_step_roofline.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    got = _run(trace=True, cell=cell)["metrics"]
    # blocks as the program counts them: K and V of four tokens, of one
    # full layer and of two window layers
    full, window = 2 * 1 * 4 * 2 * 16 * 4, 2 * 2 * 4 * 2 * 16 * 4
    held = got["kv_window_live_gb.tokens"]["value"] * 1e9 / window
    assert held == pytest.approx(round(held)) and 1 <= held <= 4 * 3 + 1
    both = got["kv_live_gb.tokens"]["value"] * 1e9
    rest = (both - held * window) / full
    assert rest == pytest.approx(round(rest)) and rest >= held / 3
    # 4 of 8 experts held: about half the pairs, as the router deals
    assert 20.0 < got["expert_pairs_held_pct.tokens"]["value"] < 80.0
    assert got["decode_batch_avg.tokens"]["value"] >= 1.0
    assert "decode_step_roofline.tokens" not in got     # no TPU plane


def test_a_program_without_the_family_fails_cleanly(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    `llm/spec.py` has no such family, and the runner says so before any
    weight is made."""
    from nnstreamer_tpu.llm import spec
    from perfbench.runners import window_moe_llm

    monkeypatch.delattr(spec, "WINDOW_MOE")
    with pytest.raises(harness.HarnessError, match="no window family"):
        window_moe_llm.Runner(tiny.cell(), SEED, 1.0, False, [])


def test_readers_on_planted_readings():
    ctx = {"counters": {"start": {"window_blocks_used": 3,
                                  "expert_pairs_held": 100,
                                  "expert_pairs_away": 700},
                        "end": {"window_blocks_used": 1040,
                                "expert_pairs_held": 1100,
                                "expert_pairs_away": 7700}},
           "window_block_bytes": 64 * 16384}
    assert kv_window_live_gb.read(ctx) == pytest.approx(1.09051904)
    assert kv_window_live_gb.read({"counters": ctx["counters"]}) is None
    assert expert_pairs_held_pct.read(ctx) == pytest.approx(12.5)
    # a program whose counters lack the pairs: nothing to read
    assert expert_pairs_held_pct.read(
        {"counters": {"start": {}, "end": {}}}) is None
    assert expert_pairs_held_pct.read(
        {"counters": {"start": {"expert_pairs_held": 5,
                                "expert_pairs_away": 5},
                      "end": {"expert_pairs_held": 5,
                              "expert_pairs_away": 5}}}) is None
