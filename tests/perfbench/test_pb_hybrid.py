"""The benchmark's files for the hybrid family (linear attention with a
carried state, block-sparse attention over compressed keys): the
configuration against its source, the mix and the cell against the issue's
numbers, the parameter count, the cost functions against hand counts, a
whole tiny run through the new runner, the two new readers."""

import json
import os
import time

import pytest

import tiny_hybrid as tiny
from perfbench import costs, costs_hybrid_lm, harness, run, traffic
from perfbench.layer_metrics import state_live_gb, state_share_of_step_pct
from perfbench.references import hybrid_lm

SEED = 2**31 + 29
CELL = "sala_longdoc_backlog"


def _sala():
    with open(os.path.join(harness.ROOT, "perfbench/configs",
                           "minicpm-sala-8l.json")) as f:
        return json.load(f)


SALA = _sala()

MIXERS = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
          + ["lightning-attn"] * 6 + ["minicpm4"] * 2
          + ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6
          + ["minicpm4"] * 3)

# openbmb/MiniCPM-SALA config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": MIXERS, "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True}


def test_sala_file_holds_the_published_numbers():
    assert len(MIXERS) == 32 and MIXERS.count("minicpm4") == 8
    differ = {k for k, v in PUBLISHED.items() if SALA.get(k, "absent") != v}
    assert differ == set(SALA["reduced"]) == {"num_hidden_layers",
                                              "mixer_types"}
    assert SALA["num_hidden_layers"] == 8
    # the published layers 9-16: one sparse to three linear, as 8 : 24
    assert SALA["mixer_types"] == MIXERS[9:17] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    assert SALA["published"] == {"num_hidden_layers": 32,
                                 "mixer_types": MIXERS}
    assert set(SALA["reduced_why"]) == set(SALA["reduced"])
    assert SALA["source"].endswith("openbmb/MiniCPM-SALA/blob/main/config.json")
    assert SALA["assumed_sizes"] == {
        "sparse_kernel_size": 32, "sparse_kernel_stride": 16,
        "sparse_block_size": 64, "sparse_topk": 64,
        "sparse_window_size": 2048, "sparse_init_blocks": 1}
    for key in ("sparse_geometry", "sparse_scores", "linear_decay",
                "qk_norm", "gates", "output_norm", "rope", "mup", "state",
                "weights"):
        assert len(SALA["assumed"][key]) > 40       # each with its reason
    assert "4 TPU v5e chips in a pipeline of 8 layers" in SALA["deployment"]
    serving = dict(SALA["serving"])
    assert serving.pop("pool_reserve_bytes") > 0
    assert serving == {
        "max_batch": 32, "max_len": 66560, "block_size": 16,
        "paged_kernel": "xla", "prefill_chunk": 2048, "chunk_every": 4,
        "eos_id": -1, "admit_window_ms": 0.5}
    assert SALA["kernels"] == {"decode_step": "jit_hybrid_decode_step",
                               "prefill": "jit_hybrid_prefill_chunk"}
    assert SALA["check"]["sample_requests"] == 3
    assert list(SALA["check"]["limits"]) == ["served_token_gap_mean"]
    assert SALA["check"]["controls"] == ["fp8"]


def test_sala_dims_and_scalings():
    m = hybrid_lm.dims(SALA)
    assert m["kinds"] == ("sparse",) + ("linear",) * 6 + ("sparse",)
    assert m["r"] == pytest.approx(1.4 / 32 ** 0.5)     # published depth
    assert m["emb_scale"] == 12 and m["logit_div"] == 16
    assert (m["kernel"], m["stride"], m["block"], m["topk"], m["window"],
            m["init"]) == (32, 16, 64, 64, 2048, 1)


def test_sala_parameter_count():
    n = hybrid_lm.param_count(SALA)
    assert n["sparse"] == {"attention": 35651584, "gate": 16777216,
                           "mlp": 201326592, "norms": 8448}
    assert n["linear"] == {"attention": 67108864, "gate": 16777216,
                           "mlp": 201326592, "norms": 12544}
    assert n["per_layer"] == {"sparse": 253763840, "linear": 285225216}
    assert n["outside"] == 601686016
    total = (2 * n["per_layer"]["sparse"] + 6 * n["per_layer"]["linear"]
             + n["outside"] + n["final_norm"])
    assert 5.63e9 < 2 * total < 5.65e9              # bytes in bfloat16


def test_state_and_pool_bytes():
    assert costs_hybrid_lm.state_bytes_per_seq(SALA) == 12582912
    # K and V of two layers' two heads
    assert costs_hybrid_lm.kv_bytes_per_token(SALA, 2) == 2048
    # by slot: one compressed key a head, layer and 16 tokens of max_len
    assert costs_hybrid_lm.ckey_bytes_per_seq(SALA, 66560, 2) == 4259840


MATS = 2 * (35651584 + 16777216 + 201326592) \
    + 6 * (67108864 + 16777216 + 201326592)


def test_decode_step_hand_count():
    ops, nbytes = costs_hybrid_lm.decode_step(
        SALA, rows=32, ckeys_scored=60000, kv_selected=120000)
    mats = MATS + 4096 * 73448
    assert ops == (2.0 * 32 * mats + 4.0 * 32 * 6 * 32 * 128 * 128
                   + 2.0 * 60000 * 32 * 128 * 2
                   + 4.0 * 120000 * 32 * 128 * 2)
    kv_row, ck_row = 2 * 2 * 128 * 2 * 2, 2 * 128 * 2 * 2
    assert nbytes == (2 * mats + 2 * 32 * 4096 + 2 * 32 * 12582912
                      + ck_row * 60000 + kv_row * 120000 + kv_row * 32
                      + 4 * 32 * 73448)
    t, bound = costs.floor_seconds(ops, nbytes, costs.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 0.007 < t < 0.010
    # the state is about a tenth of a full step's bytes
    assert 0.08 < 2 * 32 * 12582912 / nbytes < 0.14


def test_prefill_chunk_hand_count():
    ops, nbytes = costs_hybrid_lm.prefill_chunk(
        SALA, clen=2048, pos0=14336, ckeys_scored=1900000,
        kv_selected=8300000)
    assert ops == (2.0 * 2048 * MATS + 2.0 * 4096 * 73448
                   + 4.0 * 6 * 32 * 128 * 8 * (256 * 257 / 2)
                   + 4.0 * 6 * 32 * 128 * 128 * 2048
                   + 2.0 * 1900000 * 32 * 128 * 2
                   + 4.0 * 8300000 * 32 * 128 * 2)
    assert nbytes == (2 * (MATS + 4096 * 73448) + 2 * 2048 * 4096
                      + 2 * 12582912 + 2048 * 16384 + 1024 * 1024
                      + 4 * 73448)
    t, bound = costs.floor_seconds(ops, nbytes, costs.peaks_for("TPU v5 lite"))
    assert bound == "compute" and 0.045 < t < 0.052
    # a short last chunk: one run of 100 tokens
    short, _ = costs_hybrid_lm.prefill_chunk(SALA, 100, 0, 0, 0)
    assert short == (2.0 * 100 * MATS + 2.0 * 4096 * 73448
                     + 4.0 * 6 * 32 * 128 * (100 * 101 / 2)
                     + 4.0 * 6 * 32 * 128 * 128 * 100)


def test_the_mix_and_the_cell():
    """The issue's items, arrival and rows, number for number."""
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
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == dict(cell, config="minicpm-sala-8l",
                        traffic="longdoc_backlog", chips=1)
    assert m["workloads"][-1] == cell and m["configs"][-1]["name"] == \
        "minicpm-sala-8l"
    listed = [e["name"] for e in m["end_to_end"] + m["per_layer"]
              if CELL in e.get("workloads", ())]
    assert len(listed) == 10 and "tokens_per_s" in listed
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"][-1] == CELL


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


def test_trace_run_reports_the_state_through_its_readers():
    cell = tiny.cell()
    names = ["state_live_gb.tokens", "state_share_of_step_pct.tokens",
             "kv_live_gb.tokens", "decode_batch_avg.tokens",
             "admission_blocked.tokens", "decode_step_roofline.tokens"]
    cell.per_layer = [{"name": n, "unit": "x", "moves": "tokens_per_s"}
                      for n in names]
    got = _run(trace=True, cell=cell)["metrics"]
    # rows live when the window closed x the program's own slot bytes
    # (a state of two layers, sixteen compressed keys of four heads)
    slot = 2 * 4 * 16 * 16 * 4 + 4 * 16 * 16 * 4
    assert got["state_live_gb.tokens"]["value"] * 1e9 / slot in (1, 2, 3, 4)
    assert 0.0 < got["state_share_of_step_pct.tokens"]["value"] < 100.0
    # a block as the program counts it: K and V of four tokens
    block = 2 * 2 * 4 * 2 * 16 * 4
    blocks = got["kv_live_gb.tokens"]["value"] * 1e9 / block
    assert blocks == pytest.approx(round(blocks)) and blocks >= 1
    assert got["decode_batch_avg.tokens"]["value"] >= 1.0
    assert "decode_step_roofline.tokens" not in got     # no TPU plane


def test_a_program_without_the_family_fails_cleanly(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    `llm/spec.py` has no such family, and the runner says so before any
    weight is made."""
    from nnstreamer_tpu.llm import spec
    from perfbench.runners import hybrid_llm

    monkeypatch.delattr(spec, "HYBRID")
    with pytest.raises(harness.HarnessError, match="no hybrid family"):
        hybrid_llm.Runner(tiny.cell(), SEED, 1.0, False, [])


def test_state_readers_on_planted_readings():
    ctx = {"counters": {"start": {"state_slots_used": 3},
                        "end": {"state_slots_used": 29}},
           "state_slot_bytes": 12582912}
    assert state_live_gb.read(ctx) == pytest.approx(0.364904448)
    assert state_live_gb.read({"counters": ctx["counters"]}) is None
    calls = [costs_hybrid_lm.decode_step(SALA, 32, 60000, 120000),
             costs_hybrid_lm.decode_step(SALA, 16, 30000, 60000)]
    state = [2 * 32 * 12582912, 2 * 16 * 12582912]
    share = state_share_of_step_pct.read(
        {"kernel_calls": {"decode_step": calls},
         "decode_state_bytes": state})
    assert share == pytest.approx(
        100.0 * sum(state) / (calls[0][1] + calls[1][1]))
    assert 8.0 < share < 14.0
    # a program whose spans carry no state_rows: nothing to read
    assert state_share_of_step_pct.read(
        {"kernel_calls": {"decode_step": calls},
         "decode_state_bytes": []}) is None
