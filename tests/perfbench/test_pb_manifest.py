"""BENCHMARK.json against the contract's schema, and the files it names."""

import importlib
import json
import os
import re

import pytest

from perfbench import harness

ROOT = harness.ROOT
MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert MANIFEST["command"][-1].startswith(tuple(MANIFEST["paths"]))


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    for w in m.get("workloads", []):
        assert w in CELLS
    if "bound" in m:                      # end to end
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_names_are_unique():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(m):
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e and m["moves"] != "setup_s"
    target = e2e[m["moves"]]
    for cell in m.get("workloads", CELLS):
        assert "workloads" not in target or cell in target["workloads"]
    # the tag names the end-to-end metric, so one name never spans cells
    # that report different ones
    base = m["name"].rsplit(".", 1)[0]
    mod = importlib.import_module(f"perfbench.layer_metrics.{base}")
    assert callable(mod.read) and mod.read({}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports(cell):
    c = harness.resolve_cell(MANIFEST, cell)
    assert c.chips in (1, 4)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert len(c.per_layer) >= 1
    importlib.import_module(f"perfbench.runners.{c.config['runner']}")
    importlib.import_module(f"perfbench.references.{c.config['reference']}")
    w = {w["name"]: w for w in MANIFEST["workloads"]}[cell]
    assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert cfg["file"].startswith(tuple(MANIFEST["paths"]))
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert cfg["name"] in used


def test_ouro_file_holds_the_published_numbers():
    """Every number of ByteDance/Ouro-2.6B's config.json under its own
    key; only the keys in `reduced` differ."""
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152}
    with open(os.path.join(ROOT, "perfbench/configs/ouro-2.6b-1pass.json")) as f:
        body = json.load(f)
    differ = {k for k, v in published.items() if body.get(k) != v}
    assert differ == set(body["reduced"])
    assert len(body["layer_types"]) == 48


def test_unknown_workload_is_refused():
    with pytest.raises(harness.HarnessError):
        harness.resolve_cell(MANIFEST, "no_such_cell")


READERS = sorted(f[:-3] for f in os.listdir(
    os.path.join(ROOT, "perfbench", "layer_metrics"))
    if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("base", READERS)
def test_reader_with_nothing_to_read_returns_nothing(base):
    mod = importlib.import_module(f"perfbench.layer_metrics.{base}")
    assert mod.read({}) is None
    assert mod.read({"counters": {}, "trace_window": None,
                     "kernel_calls": {}, "gen_lag_s": []}) is None


def test_counter_readers_read_the_window_only():
    from perfbench.layer_metrics import (admission_blocked,
                                         compiles_in_window,
                                         decode_batch_avg, kv_live_gb)

    ctx = {"counters": {
        "start": {"compile_count": 11, "decode_tokens": 100,
                  "decode_steps": 10, "admission_blocked": 2},
        "end": {"compile_count": 11, "decode_tokens": 340,
                "decode_steps": 30, "admission_blocked": 5,
                "kv_blocks_used": 100}},
        "kv_block_bytes": 12582912}
    assert compiles_in_window.read(ctx) == 0
    assert decode_batch_avg.read(ctx) == 12.0
    assert admission_blocked.read(ctx) == 3
    assert kv_live_gb.read(ctx) == pytest.approx(1.2582912)


def test_answer_stall_is_the_longest_silence_of_the_window():
    from perfbench.layer_metrics import answer_stall_max_ms

    assert answer_stall_max_ms.read(
        {"answer_times": [10.0, 10.5, 10.6, 12.0, 12.1]}) == \
        pytest.approx(1400.0)
