"""The op and byte functions against hand counts."""

import json
import os

import pytest

from perfbench import costs, harness
from perfbench.references import decoder_lm


def _cfg(name):
    with open(os.path.join(harness.ROOT, "perfbench/configs", name)) as f:
        return json.load(f)


OURO = _cfg("ouro-2.6b-1pass.json")


def test_ouro_parameter_count():
    layer = 2048 * 6144 + 2048 * 2048 + 2048 * 11264 + 5632 * 2048
    want = 48 * (layer + 2 * 2048) + 2 * 49152 * 2048 + 2048
    assert decoder_lm.param_count(OURO) == want
    assert 2.6e9 < want < 2.7e9


def test_decode_step_hand_count():
    layer = 2048 * 6144 + 2048 * 2048 + 2048 * 11264 + 5632 * 2048
    mats = 48 * layer + 2048 * 49152
    ops, nbytes = costs.lm_decode_step(OURO, rows=8, kv_tokens=4000)
    assert ops == 2.0 * 8 * mats + 4.0 * 4000 * 2048 * 48
    kv_row = 2 * 48 * 2048 * 2
    assert nbytes == (2 * mats + 2 * 8 * 2048 + kv_row * 4000 + kv_row * 8
                      + 4 * 8 * 49152)
    peaks = costs.peaks_for("TPU v5 lite")
    t, bound = costs.floor_seconds(ops, nbytes, peaks)
    assert bound == "memory" and 0.006 < t < 0.012


def test_prefill_hand_count():
    layer = 2048 * 6144 + 2048 * 2048 + 2048 * 11264 + 5632 * 2048
    ops, nbytes = costs.lm_prefill(OURO, 512)
    want = (2.0 * 512 * 48 * layer + 2.0 * 2048 * 49152
            + 4.0 * (512 * 513 / 2) * 2048 * 48)
    assert ops == want
    peaks = costs.peaks_for("TPU v5 lite")
    assert costs.floor_seconds(ops, nbytes, peaks)[1] == "compute"


def test_kv_bytes_per_token_of_the_float32_pool():
    assert costs.lm_kv_bytes_per_token(OURO, 4) == 786432


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks_for("TPU v99")
    assert costs.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
