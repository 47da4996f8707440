"""`pallas_ops.grouped_matmul` (interpreted here) against
`jax.lax.ragged_dot`, the expert layer on either side of the count of
pair rows where it changes product, the rule that gives the row tile,
and the counters that say how full the visited tiles were."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_sparse_moe                                          # noqa: E402
import tiny_window_moe                                          # noqa: E402
from nnstreamer_tpu.backends import pallas_ops                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.llm import experts, families                # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import sparse_moe_lm, window_moe_lm   # noqa: E402
from perfbench.runners import sparse_moe_llm, window_moe_llm    # noqa: E402

TM = 16

# group sizes over row tiles of 16, and the rows lhs has
CASES = {
    "empty-groups-between-full-ones": ([16, 0, 0, 32, 0, 16], 64),
    "a-group-straddles-two-tiles": ([5, 20, 7], 32),
    "a-group-straddles-three-tiles": ([9, 35, 4], 48),
    "all-rows-at-one-expert": ([0, 0, 64, 0], 64),
    "rows-past-the-last-group": ([3, 0, 10], 48),
    "rows-no-multiple-of-the-tile": ([11, 0, 17, 9], 41),
    "no-group-has-a-row": ([0, 0, 0], 32),
}


def _operands(sizes, m, dtype, k=256, n=384):
    rng = np.random.default_rng(m + len(sizes))
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)) * k ** -0.5, dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_equals_ragged_dot_on_every_groups_rows(case):
    sizes, m = CASES[case]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    got = pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(TM, 128, 128))
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(lhs, rhs, counts)
    held = sum(sizes)
    assert got.shape == want.shape == (m, 384) and got.dtype == jnp.float32
    assert np.allclose(np.asarray(got)[:held], np.asarray(want)[:held],
                       atol=2e-5, rtol=0)


@pytest.mark.parametrize("case", sorted(CASES))
def test_bfloat16_is_within_its_rounding_of_ragged_dot(case):
    sizes, m = CASES[case]
    lhs, rhs, counts = _operands(sizes, m, jnp.bfloat16)
    got = pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(TM, 128, 384))
    want = jax.lax.ragged_dot(lhs, rhs, counts)
    held = sum(sizes)
    assert got.dtype == jnp.bfloat16
    # values of order 1, summed in float32 and rounded once: a bfloat16
    # step at 4 is 2 ** -5
    diff = np.abs(np.asarray(got[:held], np.float32)
                  - np.asarray(want[:held], np.float32))
    assert diff.size == 0 or diff.max() <= 2 ** -5


def test_rows_past_the_last_group_are_never_written():
    """The contract: what no group owns is not defined. The interpreter
    leaves NaN in unwritten output, so a reader of those rows shows."""
    sizes, m = CASES["rows-past-the-last-group"]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    got = np.asarray(pallas_ops.grouped_matmul(lhs, rhs, counts,
                                               tiling=(TM, 128, 128)))
    assert np.isfinite(got[:13]).all()
    assert np.isnan(got[16:]).all()       # tiles no visit reached


@pytest.mark.parametrize("sizes,m,tm,want", [
    ([32], 64, 64, [(0, 0)]),                       # inside one tile
    ([20, 30], 64, 32, [(0, 0), (1, 0), (1, 1)]),   # the second straddles
    ([0, 70, 0, 2], 128, 32, [(1, 0), (1, 1), (1, 2), (3, 2)]),
    ([0, 0], 32, 16, [])])
def test_the_visits_are_the_tile_and_group_pairs_that_share_rows(sizes, m,
                                                                 tm, want):
    _, group, tile, visits = pallas_ops.group_visits(
        jnp.asarray(sizes, jnp.int32), m, tm)
    n = int(visits)
    assert group.shape == tile.shape == (m // tm + len(sizes) - 1,)
    assert list(zip(np.asarray(group)[:n].tolist(),
                    np.asarray(tile)[:n].tolist())) == want
    assert n == families.expert_tile_visits(np.asarray([sizes]), tm)


def test_a_tile_that_does_not_divide_is_refused():
    lhs, rhs, counts = _operands([8, 8], 16, jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(16, 96, 128))


# -- the rule ------------------------------------------------------------------

@pytest.mark.parametrize("rows,n_experts,tile", [
    (8192, 256, 128),      # a Trinity chunk: 2,048 tokens x 4 over 256
    (16384, 128, 256),     # a Keye chunk: 2,048 x 8 over 128
    (16384, 256, 128),     # a whole Trinity prompt of 4,096
    (1024, 256, 128),      # Trinity's bucket of 256
    (1024, 128, 128),      # Keye's bucket of 128
    (512, 128, 512),       # Keye's bucket of 64: the compiler's, all rows
    (64, 256, 64), (128, 128, 128),        # decode steps of 16 rows
    (4, 256, 8)])          # one row of Trinity, filled to 8
def test_the_row_tile_comes_from_the_shapes(rows, n_experts, tile):
    assert experts.expert_row_tile(rows, n_experts) == tile


@pytest.mark.parametrize("size,want,tile", [
    (3072, 1024, 1024), (6144, 1024, 1024), (2048, 1024, 1024),
    (1536, 1024, 768), (768, 1024, 768), (768, 512, 384), (64, 1024, 64),
    (200, 128, 200)])
def test_k_and_n_tiles_divide_their_dimension(size, want, tile):
    assert experts.fit(size, want) == tile


# -- the layer on both sides of the switch-over ------------------------------------

def _calls_the_kernel(monkeypatch):
    seen = []
    real = pallas_ops.grouped_matmul

    def spy(lhs, rhs, counts, *, tiling, interpret=None):
        seen.append((lhs.shape[0], tiling))
        return real(lhs, rhs, counts, tiling=tiling, interpret=interpret)

    monkeypatch.setattr(pallas_ops, "grouped_matmul", spy)
    return seen


@pytest.mark.parametrize("n,real,kernel", [(24, 21, False), (256, 250, False),
                                           (264, 264, True), (320, 301, True)])
def test_the_sparse_expert_layer_is_the_references_either_side(
        monkeypatch, n, real, kernel):
    """Keye's tiny layer (8 experts, 2 a token) at 48 and 512 pair rows,
    which keep `ragged_dot`, and at 528 and 640, which take the kernel:
    every expert on every token, weighted, as the reference has it."""
    seen = _calls_the_kernel(monkeypatch)
    cfg = tiny_sparse_moe.CONFIG
    spec = sparse_moe_llm.lm_spec(cfg)
    blk = sparse_moe_lm.make_params(cfg, 2**31 + 5,
                                    dtype=jnp.float32)["blocks"][0]
    g = jnp.asarray(np.random.default_rng(n).normal(size=(n, 64)),
                    jnp.float32)
    live = jnp.arange(n) < real
    y, counts, away = experts.expert_layer(blk, g, live, spec,
                                               jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(sparse_moe_lm.moe_dense(g, blk, 2))
    assert len(seen) == (2 if kernel else 0)
    assert np.abs(np.asarray(y)[:real] - want[:real]).max() < 2e-5
    # the padding rows read the unwritten rows behind the mask only
    assert np.isfinite(np.asarray(y)).all()
    assert np.abs(np.asarray(y)[real:]).sum() == 0.0
    assert int(counts.sum()) == 2 * real and int(away) == 0


@pytest.mark.parametrize("n,kernel", [(64, False), (384, True)])
def test_the_window_familys_share_is_the_references_either_side(
        monkeypatch, n, kernel):
    """Trinity's tiny layer told its share (2 held of 8, 2 a token): 128
    pair rows keep `ragged_dot`, 768 take the kernel, where three
    quarters of the pairs are routed away and lie past the last group."""
    seen = _calls_the_kernel(monkeypatch)
    cfg = dict(tiny_window_moe.CONFIG, num_experts=8,
               expert_share={"published": 8, "first": 0})
    spec = window_moe_llm.lm_spec(tiny_window_moe.CONFIG)
    whole = window_moe_lm.make_params(cfg, 2**31 + 7,
                                      dtype=jnp.float32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(n).normal(size=(n, 64)),
                    jnp.float32)
    first = 4
    share = dict(whole, ewi=whole["ewi"][first:first + 2],
                 ewd=whole["ewd"][first:first + 2])
    spec = dataclasses.replace(spec, experts_first=first, experts_held=2)
    y, counts, away = experts.expert_layer(
        share, u, jnp.ones((n,), bool), spec, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = window_moe_lm.routed_part(u, share, first=first, k=2,
                                            scale=2.448)
    assert len(seen) == (2 if kernel else 0)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 2e-5
    assert int(counts.sum()) + int(away) == 2 * n and int(away) > n


# -- the counters ------------------------------------------------------------------

@pytest.mark.parametrize("counts,tm,visits", [
    ([[32, 0, 0]], 128, 1),            # 32 rows inside one tile
    ([[100, 60, 0]], 128, 3),          # the second straddles the edge
    ([[0, 0, 0]], 128, 0),             # nobody
    ([[128, 128], [1, 300]], 128, 6),  # 2 whole tiles; then 1 + 3
])
def test_expert_tile_visits_counts_the_tiles_each_experts_rows_span(
        counts, tm, visits):
    assert families.expert_tile_visits(np.asarray(counts), tm) == visits


def _sparse_executor(**kw):
    cfg = tiny_sparse_moe.CONFIG
    params = sparse_moe_lm.make_params(cfg, 2**31 + 5, dtype=jnp.float32)
    return PagedLLMExecutor(
        ModelBundle(fn=None, params=params, lm=sparse_moe_llm.lm_spec(cfg)),
        dtype=jnp.float32, block_size=8, num_blocks=40, max_len=64, **kw)


def _window_set():
    cfg = tiny_window_moe.CONFIG
    params = window_moe_lm.make_params(cfg, 2**31 + 7, dtype=jnp.float32)
    return PagedLLMExecutor(
        ModelBundle(fn=None, params=params, lm=window_moe_llm.lm_spec(cfg)),
        dtype=jnp.float32, state_slots=4, prefill_chunk=4, block_size=4,
        num_blocks=48, max_len=64).programs


def test_note_beside_reckons_a_chunks_visits_from_its_counts():
    """Hand-made counts of a chunk padded to 1,024 tokens of Keye's tiny
    layer (2 a token over 8 experts: 2,048 pair rows, a row tile of 256):
    an expert of 32 rows inside one tile is one visit, the next one's
    250 rows straddle the tile's edge for two, an empty one is none."""
    ps = _sparse_executor().programs
    counts = np.zeros((2, 8), np.int32)
    counts[0, :3] = 32, 250, 0
    counts[1, 5] = 600                       # rows 0-599: three tiles
    said = ps.note_beside("chunk", [counts], 1024)
    assert said["expert_tile_visits"] == 1 + 2 + 3
    assert said["expert_tile_fill_pct"] == round(100 * 882 / (6 * 256), 2)
    assert ps.counters["expert_tile_visits"] == 6
    assert ps.counters["expert_tile_rows"] == 6 * 256
    # a bucket whose pair rows the compiler's tile holds whole: a visit
    # an expert with rows, each all the 16 pair rows
    said = ps.note_beside("chunk", [counts[:1] // 32], 8)
    assert said == {"experts_touched": 2, "expert_load_max": 7,
                    "expert_tile_visits": 2,
                    "expert_tile_fill_pct": round(100 * 8 / 32, 2)}
    # a decode step's products are not a chunk's: nothing is counted
    ps.note_beside("decode", [counts[:, :] // 300])
    assert ps.counters["expert_tile_visits"] == 8


def test_the_window_set_counts_held_experts_visits():
    """The window family's counts carry the pairs routed away last:
    they are past the last group and no visit."""
    ps = _window_set()
    held = ps.held
    load = np.zeros((2, held + 1), np.int32)
    load[0, 0], load[0, -1] = 32, 900
    load[1, :2] = 100, 60
    said = ps.note_beside("chunk", [load], 512)   # 1,024 pair rows: 128
    tm = experts.expert_row_tile(512 * 2, ps.spec.n_experts)
    want = 1 + families.expert_tile_visits(load[1:, :-1], tm)
    assert said["expert_tile_visits"] == want
    assert said["expert_tile_fill_pct"] == round(100 * 192 / (want * tm), 2)
    assert ps.stats()["expert_tile_rows"] == want * tm


def test_a_served_chunks_span_says_how_full_its_tiles_were():
    tracer = Tracer(max_events=1024)
    ex = _sparse_executor(tracer=tracer, name="llm")
    ids = np.random.default_rng(0).integers(0, 256, 8).astype(np.int32)
    table = ex.cache.allocator.alloc(2)
    ex.prefill_chunk(ids, 0, table, bucket=8)            # compiles
    ex.prefill_chunk(ids, 0, table, bucket=8, req="r")
    span, = [a for ph, cat, _, label, _, _, a in tracer.events()
             if ph == "X" and label == "invoke" and a.get("req") == "r"]
    # 16 pair rows in one tile of 16: every expert with a token a visit
    layers = tiny_sparse_moe.CONFIG["num_hidden_layers"]
    assert span["expert_tile_visits"] == span["experts_touched"]
    assert span["expert_tile_fill_pct"] == round(
        100 * layers * 16 / (span["expert_tile_visits"] * 16), 2)
    stats = ex.stats()
    assert stats["expert_tile_rows"] == 16 * stats["expert_tile_visits"] > 0
