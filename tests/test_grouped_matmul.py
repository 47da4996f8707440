"""`pallas_ops.grouped_matmul` (interpreted here) against
`jax.lax.ragged_dot` and a dense product, the expert layer on either side
of the count of pair rows where its tiles change, the rule that gives the
row tile, and the counters that say what the products visited."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_delta_moe                                           # noqa: E402
import tiny_sparse_moe                                          # noqa: E402
import tiny_window_moe                                          # noqa: E402
from nnstreamer_tpu.backends import pallas_ops                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.llm import experts, families                # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import (                              # noqa: E402
    delta_moe_lm, sparse_moe_lm, window_moe_lm)
from perfbench.runners import (                                 # noqa: E402
    delta_moe_llm, sparse_moe_llm, window_moe_llm)

# row tiles of a few rows: a float32 and a bfloat16 sublane tile and the
# next
TILES = [8, 16, 32]

# group sizes, and the rows lhs has: over row tiles of 16 as the names say,
# and other straddles over tiles of 8 and of 32
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


def _dense(lhs, rhs, sizes):
    """Every group's rows against its matrix, one product a group, in
    float64 on the host: the plain reading of the grouped product."""
    lhs, rhs = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    ends = np.cumsum(sizes)
    return np.concatenate(
        [lhs[e - c:e] @ rhs[g] for g, (c, e) in enumerate(zip(sizes, ends))]
        + [np.zeros((0, rhs.shape[2]))])


@pytest.mark.parametrize("tm", TILES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_equals_ragged_dot_on_every_groups_rows(case, tm):
    sizes, m = CASES[case]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    got = pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(tm, 128, 128))
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(lhs, rhs, counts)
    held = sum(sizes)
    assert got.shape == want.shape == (m, 384) and got.dtype == jnp.float32
    assert np.allclose(np.asarray(got)[:held], np.asarray(want)[:held],
                       atol=2e-5, rtol=0)
    assert np.allclose(np.asarray(got)[:held], _dense(lhs, rhs, sizes),
                       atol=2e-5, rtol=0)


@pytest.mark.parametrize("tm", TILES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_bfloat16_is_within_its_rounding_of_the_dense_product(case, tm):
    sizes, m = CASES[case]
    lhs, rhs, counts = _operands(sizes, m, jnp.bfloat16)
    got = pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(tm, 128, 384))
    held = sum(sizes)
    assert got.dtype == jnp.bfloat16
    # values of order 1, summed in float32 and rounded once: a bfloat16
    # step at 4 is 2 ** -5, and half of it the rounding
    diff = np.abs(np.asarray(got[:held], np.float64)
                  - _dense(lhs, rhs, sizes))
    assert diff.size == 0 or diff.max() <= 2 ** -6


@pytest.mark.parametrize("tm", TILES)
def test_rows_past_the_last_group_are_never_written(tm):
    """The contract: what no group owns is not defined. The interpreter
    leaves NaN in unwritten output, so a reader of those rows shows."""
    sizes, m = CASES["rows-past-the-last-group"]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    got = np.asarray(pallas_ops.grouped_matmul(lhs, rhs, counts,
                                               tiling=(tm, 128, 128)))
    assert np.isfinite(got[:13]).all()
    # the tiles no visit reached: those past the 13 rows' last
    assert np.isnan(got[-(-13 // tm) * tm:]).all()


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


def test_a_reckoned_call_computes_the_same():
    """`reckoned` hands the compiler the call's cost and changes nothing
    of what it computes."""
    sizes, m = CASES["a-group-straddles-two-tiles"]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    plain, told = (np.asarray(pallas_ops.grouped_matmul(
        lhs, rhs, counts, tiling=(16, 128, 128), reckoned=r))
        for r in (False, True))
    assert np.array_equal(plain, told)


def test_a_kernel_is_interpreted_where_the_default_device_is_no_tpu(
        monkeypatch):
    """An engine kept on the host's CPU device beside the chip
    (`chip_smoke.py`'s references, under `jax.default_device`) traces
    its kernels interpreted though the process's backend is the TPU:
    the expert layer takes the kernel at every count of rows since PR
    46, so that engine meets it at its first chunk."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not pallas_ops._interpret()
    sizes, m = CASES["a-group-straddles-two-tiles"]
    lhs, rhs, counts = _operands(sizes, m, jnp.float32)
    for device in (jax.devices("cpu")[0], "cpu"):
        with jax.default_device(device):
            assert pallas_ops._interpret()
            got = pallas_ops.grouped_matmul(lhs, rhs, counts,
                                            tiling=(16, 128, 128))
    assert np.allclose(np.asarray(got), _dense(lhs, rhs, sizes), atol=2e-5)


def test_a_tile_that_does_not_divide_is_refused():
    lhs, rhs, counts = _operands([8, 8], 16, jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        pallas_ops.grouped_matmul(lhs, rhs, counts, tiling=(16, 96, 128))


# -- the rule ------------------------------------------------------------------

BF, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("rows,n_experts,tile", [
    (8192, 256, 128),      # a Trinity chunk: 2,048 tokens x 4 over 256
    (16384, 128, 256),     # a Keye chunk: 2,048 x 8 over 128
    (16384, 256, 128),     # a whole Trinity prompt of 4,096
    (1024, 256, 128),      # Trinity's bucket of 256
    (1024, 128, 128),      # Keye's bucket of 128
    (512, 256, 64),        # Kimi-Linear's decode bucket of 64 rows x 8
    (256, 256, 64),        # and of 32
    (192, 160, 64),        # DeepSeek-V2's of 32 rows x 6
    (512, 128, 64),        # Keye's chunk bucket of 64 tokens
    (64, 256, 64), (64, 128, 64),      # Trinity's 16 rows, Keye's 8
    (6, 160, 64), (4, 256, 64)])       # one row of DeepSeek-V2, of Trinity
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

    def spy(lhs, rhs, counts, *, tiling, **kw):
        seen.append((lhs.shape[0], tiling))
        return real(lhs, rhs, counts, tiling=tiling, **kw)

    monkeypatch.setattr(pallas_ops, "grouped_matmul", spy)
    return seen


@pytest.mark.parametrize("n,real,tile", [(1, 1, 64), (24, 21, 64),
                                         (256, 250, 64), (264, 264, 256),
                                         (320, 301, 256)])
def test_the_sparse_expert_layer_is_the_references_either_side(
        monkeypatch, n, real, tile):
    """Keye's tiny layer (8 experts, 2 a token) at 2, 48 and 512 pair
    rows, a decode bucket's and a small chunk's, and at 528 and 640, a
    chunk's: both products through the kernel at the rule's row tile,
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
    assert [(rows, t[0]) for rows, t in seen] == [(2 * n, tile)] * 2
    assert np.abs(np.asarray(y)[:real] - want[:real]).max() < 2e-5
    # the padding rows read the unwritten rows behind the mask only
    assert np.isfinite(np.asarray(y)).all()
    assert np.abs(np.asarray(y)[real:]).sum() == 0.0
    assert int(counts.sum()) == 2 * real and int(away) == 0


@pytest.mark.parametrize("n,tile", [(2, 64), (64, 64), (384, 256)])
def test_the_window_familys_share_is_the_references_either_side(
        monkeypatch, n, tile):
    """Trinity's tiny layer told its share (2 held of 8, 2 a token) at 4,
    128 and 768 pair rows, where three quarters of the pairs are routed
    away and lie past the last group."""
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
    assert [t[0] for _, t in seen] == [tile, tile]
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 2e-5
    assert int(counts.sum()) + int(away) == 2 * n and int(away) >= n


@pytest.mark.parametrize("n,real,dtype,tile", [
    (8, 8, F32, 64), (64, 63, F32, 64), (128, 128, F32, 128),
    (64, 64, BF, 64)],
    ids=["8-rows", "64-rows", "128-rows-a-chunks-tile", "64-rows-bfloat16"])
def test_a_kimi_shaped_bucket_is_the_references_either_side(
        monkeypatch, n, real, dtype, tile):
    """Kimi-Linear's expert layer at its shape and a small width (256
    experts of which 64 are held, 8 a token): its decode buckets of 8 and
    64 rows (64 and 512 pair rows, about a quarter held and sorted first,
    the rest past the last group) at the row tile of 64, a bucket of 128
    past `FEW_ROWS` at a chunk's; every held expert on every real token,
    weighted, as the reference has it."""
    seen = _calls_the_kernel(monkeypatch)
    cfg = dict(tiny_delta_moe.CONFIG, num_experts=64,
               num_experts_per_token=8,
               expert_share={"published": 256, "first": 64})
    spec = delta_moe_llm.lm_spec(cfg)
    blk = delta_moe_lm.make_params(cfg, 2**31 + 11,
                                   dtype=F32)["blocks"][1]
    u = jnp.asarray(np.random.default_rng(n).normal(size=(n, 64)), F32)
    live = jnp.arange(n) < real
    y, counts, away = experts.expert_layer(
        jax.tree_util.tree_map(lambda a: a.astype(dtype), blk),
        u.astype(dtype), live, spec, dtype)
    with jax.default_matmul_precision("highest"):
        want, _ = delta_moe_lm.routed_part(u, blk, delta_moe_lm.dims(cfg))
    want = np.asarray(want)
    assert [(rows, t[0]) for rows, t in seen] == [(8 * n, tile)] * 2
    assert (8 * n <= experts.FEW_ROWS) == (tile == 64)
    if dtype == F32:
        assert np.abs(np.asarray(y)[:real] - want[:real]).max() < 2e-5
        assert int(counts.sum()) + int(away) == 8 * real
        assert 0 < int(counts.sum()) < 8 * real // 2
    else:
        # bfloat16 may route a near tie elsewhere: most rows agree within
        # their rounding
        off = np.abs(np.asarray(y, np.float32) - want).max(axis=1)
        assert np.median(off) < 0.03 * np.abs(want).max()
    # the padding rows read the unwritten rows behind the mask only
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert np.abs(np.asarray(y, np.float32)[real:]).sum() == 0.0


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
    # a bucket of 8 tokens: 16 pair rows inside one row tile of 64, a
    # visit an expert with rows
    said = ps.note_beside("chunk", [counts[:1] // 32], 8)
    assert said == {"experts_touched": 2, "expert_load_max": 7,
                    "expert_tile_visits": 2,
                    "expert_tile_fill_pct": round(100 * 8 / 128, 2)}
    # a decode step's visits are counted as a chunk's, and its span says
    # the row tile: 64 rows x 2 are 128 pair rows in two tiles of 64, and
    # the second expert's rows straddle the edge between them
    counts[0, :3], counts[1, 5] = (60, 10, 0), 128
    said = ps.note_beside("decode", [counts], 64)
    assert said == {"experts_touched": 3, "expert_tile_visits": 1 + 2 + 2,
                    "expert_row_tile": 64}
    assert ps.counters["expert_tile_visits"] == 6 + 2 + 5
    assert ps.counters["expert_tile_rows"] == 6 * 256 + 7 * 64


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
    # 16 pair rows in one tile of 64: every expert with a token a visit
    layers = tiny_sparse_moe.CONFIG["num_hidden_layers"]
    assert span["expert_tile_visits"] == span["experts_touched"]
    assert span["expert_tile_fill_pct"] == round(
        100 * layers * 16 / (span["expert_tile_visits"] * 64), 2)
    stats = ex.stats()
    assert stats["expert_tile_rows"] == 64 * stats["expert_tile_visits"] > 0
