"""The plain references against the program's own models at tiny sizes,
and their lower-precision controls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from perfbench.references import decoder_lm


@pytest.fixture(scope="module")
def lm():
    params = decoder_lm.make_params(tiny.LM_CONFIG, 2**31 + 3,
                                    dtype=jnp.float32)
    return params


def test_lm_params_have_the_programs_layout(lm):
    from nnstreamer_tpu.models import transformer as T

    theirs = T.init_params(seed=0, d_model=64, n_heads=4, n_layers=2,
                           d_ff=160, vocab=256)
    assert (jax.tree_util.tree_structure(lm)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(lm),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape


def test_lm_reference_agrees_with_models_transformer(lm):
    from nnstreamer_tpu.models import transformer as T

    ids = np.random.default_rng(0).integers(0, 256, 37).astype(np.int32)
    ref = np.asarray(decoder_lm.forward_logits(lm, tiny.LM_CONFIG, ids,
                                               pad_to=16))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(T.apply_seq(lm, ids[None], n_heads=4,
                                     dtype=jnp.float32, attn="xla"))[0]
    assert ref.shape == (37, 256)
    assert np.abs(ref - got).max() < 1e-4


def test_lm_padding_does_not_change_real_positions(lm):
    ids = np.random.default_rng(1).integers(0, 256, 21).astype(np.int32)
    a = np.asarray(decoder_lm.forward_logits(lm, tiny.LM_CONFIG, ids,
                                             pad_to=8))
    b = np.asarray(decoder_lm.forward_logits(lm, tiny.LM_CONFIG, ids,
                                             pad_to=64))
    assert np.abs(a - b).max() < 1e-5


def test_lm_gap_of_the_references_own_choice_is_zero(lm):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 256, 9).astype(np.int32)
    served = []
    for _ in range(6):                        # greedy, by the reference
        ids = np.concatenate([prompt, np.asarray(served, np.int32)])
        served.append(int(np.asarray(decoder_lm.forward_logits(
            lm, tiny.LM_CONFIG, ids, pad_to=16))[-1].argmax()))
    gaps, low = decoder_lm.served_token_gaps(
        lm, tiny.LM_CONFIG, prompt, served, quants=("int8", "fp8"))
    assert gaps.shape == (6,) and float(gaps.max()) < 1e-5
    assert set(low) == {"int8", "fp8"}
    for ctl in low.values():
        assert ctl.shape == (6,) and float(ctl.min()) >= 0.0
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % 256
    gaps, _ = decoder_lm.served_token_gaps(lm, tiny.LM_CONFIG, prompt, wrong)
    assert float(gaps[3]) > 1e-3


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_lm_lower_precision_moves_the_logits(lm, quant):
    ids = np.random.default_rng(3).integers(0, 256, 32).astype(np.int32)
    ref = np.asarray(decoder_lm.forward_logits(lm, tiny.LM_CONFIG, ids))
    low = np.asarray(decoder_lm.forward_logits(lm, tiny.LM_CONFIG, ids,
                                               quant=quant))
    err = np.abs(ref - low).max()
    assert 1e-4 < err < 0.5


def test_seed_of_more_than_32_bits_makes_distinct_weights():
    a = decoder_lm.make_params(tiny.LM_CONFIG, 5, dtype=jnp.float32)
    b = decoder_lm.make_params(tiny.LM_CONFIG, 5 + 2**32, dtype=jnp.float32)
    c = decoder_lm.make_params(tiny.LM_CONFIG, 5, dtype=jnp.float32)
    assert not np.array_equal(a["head"], b["head"])
    assert np.array_equal(a["head"], c["head"])
