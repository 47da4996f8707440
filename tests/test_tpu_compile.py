"""Kernels of the served path compiled for a described TPU v5e at the
sizes the benchmark's cells run them at: what Mosaic refuses (a block
that does not tile, too much fast memory) shows here and not on the chip.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, never at import (one worker
of several loads the TPU's library; docs in the on-chip-measurement
guide), and every such compile lives in this one file.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm import experts, hybrid_lm, parts
from perfbench.references import hybrid_lm as ref
from perfbench.runners.hybrid_llm import lm_spec


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler to describe one to
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the Keye cell's chunk (keye-vl-2.0-30b-a3b-6l: 4 KV heads of 8 query
# heads of 128, chunks of 2,048, a context of 33 tiles) and a short bucket;
# the SALA cell's (minicpm-sala-8l: a call a KV head of 16 query heads, the
# keys one tile wide: `hybrid_lm.sparse_attend_walk`)
# the Trinity cell's (trinity-large-preview-5l: 8 KV heads of 6 query heads,
# chunks of 2,048 and a whole prompt of 1,024, the keys one tile wide:
# `window_moe.attend_tiles`)
@pytest.mark.parametrize("nkv,grp,c,s_pad", [
    (4, 8, 2048, 33792), (4, 8, 64, 33792), (1, 16, 2048, 1024),
    (1, 16, 64, 1024), (8, 6, 2048, 1024), (8, 6, 1024, 1024)],
    ids=["keye-2048", "keye-64", "sala-2048", "sala-64", "trinity-2048",
         "trinity-1024"])
def test_selected_block_update_compiles_at_the_cells_sizes(one_chip, nkv,
                                                           grp, c, s_pad):
    hd, tile = 128, parts.CTX_TILE

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(q, k, v, keys, t, cut, j, m, l, acc):
        return pallas_ops.selected_block_update(
            q, k, v, keys, t, cut, j, m, l, acc,
            block_q=parts.FUSED_Q_BLOCK, interpret=False)

    compiled = jax.jit(update, donate_argnums=(7, 8, 9)).lower(
        arg((nkv, grp, c, hd), jnp.bfloat16),
        arg((tile, nkv, hd), jnp.bfloat16),
        arg((tile, nkv, hd), jnp.bfloat16),
        arg((c, s_pad), jnp.uint32), arg((c,), jnp.uint32),
        arg((c,), jnp.int32), arg((), jnp.int32),
        arg((nkv, grp, c), jnp.float32), arg((nkv, grp, c), jnp.float32),
        arg((nkv, grp, c, hd), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the carry is updated in place and nothing of a tile's scores'
    # size, (heads, C, tile), is kept outside the kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < nkv * grp * c * tile


# the tile update's causal form (`window_moe.attend_tiles`,
# `latent_moe.attend_tiles`): the Trinity cell's window and full layers (8
# KV heads of 6 query heads of 128, a window of 4,096, chunks of 2,048 and
# a whole prompt of 1,024) and the DeepSeek-V2 cell's expanded heads
# (deepseek-v2-6l: 128 heads a group of one each, K filled up to 256, V
# 128 wide)
@pytest.mark.parametrize("nkv,grp,c,d,dv,window", [
    (8, 6, 2048, 128, 128, 0), (8, 6, 2048, 128, 128, 4096),
    (8, 6, 1024, 128, 128, 0), (8, 6, 1024, 128, 128, 4096),
    (128, 1, 2048, 256, 128, 0), (128, 1, 1024, 256, 128, 0)],
    ids=["trinity-2048-full", "trinity-2048-window", "trinity-1024-full",
         "trinity-1024-window", "dsv2-2048", "dsv2-1024"])
def test_causal_block_update_compiles_at_the_cells_sizes(
        one_chip, monkeypatch, nkv, grp, c, d, dv, window):
    """Mosaic takes the kernel at the block the rule gives: 128 queries
    of Trinity's 6 heads, whose blocks and scores fit the compiler's own
    16 MB of fast memory a kernel (the call asks for no more), and 1,024
    of a latent head, whose scores alone are 4 MB in float32 three times
    over: the call asks for what it reckons, under a quarter of the
    chip's 128 MiB."""
    tile, asked = parts.CTX_TILE, []
    params = pallas_ops.pltpu.CompilerParams

    def spy(**kw):
        asked.append(kw.get("vmem_limit_bytes"))
        return params(**kw)

    monkeypatch.setattr(pallas_ops.pltpu, "CompilerParams", spy)
    assert pallas_ops.causal_block_q(c, grp) == (128 if grp == 6 else 1024)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(q, k, v, qpos0, slot0, m, l, acc):
        return pallas_ops.causal_block_update(
            q, k, v, qpos0, slot0, m, l, acc, window=window,
            interpret=False)

    compiled = jax.jit(update, donate_argnums=(5, 6, 7)).lower(
        arg((nkv, grp, c, d), jnp.bfloat16),
        arg((tile, nkv, d), jnp.bfloat16),
        arg((tile, nkv, dv), jnp.bfloat16),
        arg((), jnp.int32), arg((), jnp.int32),
        arg((nkv, grp, c), jnp.float32), arg((nkv, grp, c), jnp.float32),
        arg((nkv, grp, c, dv), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "causal_block_update" in text
    if grp == 6:
        assert asked == [None]
    else:
        assert 16 << 20 < asked[0] < 32 << 20 and len(asked) == 1
    # the carry is updated in place and nothing of a tile's scores' or
    # mask's size, (heads, C, tile) or (C, tile), is kept outside
    assert not re.search(rf"(u32|s32|pred)\[{c},{tile}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < nkv * grp * c * tile


@pytest.mark.parametrize("layer,c", [(1, 2048), (2, 2048), (1, 1024)],
                         ids=["window-2048", "full-2048", "window-1024"])
def test_the_window_chunks_walk_keeps_no_mask_a_query_wide(one_chip,
                                                           monkeypatch,
                                                           layer, c):
    """One expert layer of the Trinity cell's chunk (a window layer and
    the full one; a table of 800 blocks of 64): the walk updates a tile
    in the causal kernel, which makes its mask from positions, so the
    program holds no (chunk, tile) array of integers or booleans (the
    selected form was handed one a tile: 8.4 MB, read again a KV head)."""
    from nnstreamer_tpu.llm import window_moe
    from perfbench.references import window_moe_lm
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    cfg, spec = _cell("trinity")
    kind = spec.layer_kinds[layer]
    mb, bs, nblk, bf, i32 = 800, 64, 1200, jnp.bfloat16, jnp.int32
    tile = parts.CTX_TILE

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = jax.eval_shape(
        lambda: window_moe_lm.make_params(cfg, 1, dtype=bf))["blocks"][layer]
    blk = jax.tree.map(lambda x: arg(x.shape, x.dtype), blk)
    pool = arg((4, nblk, bs, 8, 128), bf)

    def chunk_layer(*a):
        return window_moe._chunk_layer(
            *a, kind=kind, dense=False, tile=tile, by_block=True, fused=True,
            spec=spec, dtype=bf)

    text = jax.jit(chunk_layer, donate_argnums=(8, 9)).lower(
        blk, arg((c, 1, 3072), bf), arg((), i32), arg((c,), i32),
        arg((c,), jnp.bool_), arg((c,), i32), arg((c,), i32),
        arg((mb,), i32), pool, pool).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert sum("causal_block_update" in ln for ln in calls) == 1
    assert not any("selected_block_update" in ln for ln in calls)
    assert not re.search(rf"(u32|s32|pred)\[{c},{tile}\]", text)


def test_the_hybrid_chunks_walk_gathers_nothing_a_query_wide(one_chip,
                                                             monkeypatch):
    """One sparse layer of the SALA cell's chunk (minicpm-sala-8l: 2,048
    queries, 32 query / 2 KV heads of 128, 64 blocks of 64 a query, a
    table of 4,160 blocks of 16) holds no per-query gathered K or V (a
    query tile's 31 chosen blocks were (128, 2, 31 x 64, 128) before the
    walk) and no score over tokens, and updates a tile in the kernel, a
    call a KV head."""
    # the kernel for Mosaic, not interpreted (the backend here is the CPU)
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "perfbench", "configs",
                           "minicpm-sala-8l.json")) as f:
        cfg = json.load(f)
    spec = lm_spec(cfg)
    c, mb, bs, nblk, bf = 2048, 4160, 16, 8320, jnp.bfloat16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = jax.eval_shape(lambda: ref.make_params(cfg, 1, dtype=bf))
    blk = jax.tree.map(lambda x: arg(x.shape, x.dtype), blk["blocks"][0])
    pool = arg((2 * spec.n_kv, nblk, bs, 1, 128), bf)
    i32 = jnp.int32

    def layer(*a):
        return hybrid_lm._chunk_sparse(*a, spec=spec, dtype=bf,
                                       by_block=True, fused=True,
                                       tile=parts.CTX_TILE)

    text = jax.jit(layer, donate_argnums=(9, 10, 11)).lower(
        blk, arg((c, 1, 4096), bf), arg((), i32), arg((c,), i32),
        arg((), i32), arg((c,), i32), arg((c,), i32), arg((mb,), i32),
        arg((), i32), pool, pool, arg((4, 33, mb, 128), bf)
    ).compile().as_text()
    assert "1984,128]" not in text
    assert f"[{c},{mb * bs}]" not in text
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == spec.n_kv


def _cell(cell):
    """(configuration, spec) of a cell with an expert layer, by its short
    name."""
    from perfbench.runners.delta_moe_llm import lm_spec as delta_spec
    from perfbench.runners.latent_moe_llm import lm_spec as latent_spec
    from perfbench.runners.sparse_moe_llm import lm_spec as sparse_spec
    from perfbench.runners.window_moe_llm import lm_spec as window_spec
    config, runner_spec = {
        "trinity": ("trinity-large-preview-5l.json", window_spec),
        "keye": ("keye-vl-2.0-30b-a3b-6l.json", sparse_spec),
        "dsv2": ("deepseek-v2-6l.json", latent_spec),
        "kimi": ("kimi-linear-48b-a3b-8l.json", delta_spec)}[cell]
    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "perfbench", "configs", config)) as f:
        cfg = json.load(f)
    return cfg, runner_spec(cfg)


def _expert_layer_text(one_chip, cfg, spec, rows):
    """The compiled text of `experts.expert_layer` in bfloat16 at a
    cell's widths for `rows` tokens."""
    d, f_, bf = cfg["hidden_size"], spec.expert_width, jnp.bfloat16
    held = spec.experts_held or spec.n_experts

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = {"router": arg((d, spec.n_experts), bf),
           "router_bias": arg((spec.n_experts,), jnp.float32),
           "ewi": arg((held, d, 2 * f_), bf), "ewd": arg((held, f_, d), bf)}
    return jax.jit(lambda b, g, live: experts.expert_layer(
        b, g, live, spec, bf)).lower(
        blk, arg((rows, d), bf), arg((rows,), jnp.bool_)
    ).compile().as_text()


def _tiles_asked(monkeypatch):
    """The (tm, tk, tn) `pallas_ops.grouped_matmul` is called with, as
    they come."""
    seen, real = [], pallas_ops.grouped_matmul

    def spy(lhs, rhs, counts, *, tiling, **kw):
        seen.append(tuple(tiling))
        return real(lhs, rhs, counts, tiling=tiling, **kw)

    monkeypatch.setattr(pallas_ops, "grouped_matmul", spy)
    return seen


@pytest.mark.parametrize("cell,tiles", [
    ("trinity", [(128, 1024, 1024), (128, 1024, 1024)]),
    ("keye", [(256, 1024, 768), (256, 768, 1024)])])
def test_a_chunks_grouped_products_take_the_repos_kernel(one_chip,
                                                         monkeypatch, cell,
                                                         tiles):
    """The expert layer of a chunk of 2,048 tokens at the Trinity cell's
    widths (8,192 pair rows over 32 held experts of 3,072 x 6,144 and
    3,072 x 3,072) and the Keye cell's (16,384 over 128 of 2,048 x 1,536
    and 768 x 2,048): Mosaic takes `pallas_ops.grouped_matmul` at PR 40's
    tiles, a row tile of 128 or 256 and 2 MB of an expert's matrix,
    twice, as before the decode buckets took the kernel too (PR 46), and
    the compiler's own grouped product, whose row tile there is 512
    (`ragged_dot_tiling="512,...`), is gone."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    asked = _tiles_asked(monkeypatch)
    cfg, spec = _cell(cell)
    text = _expert_layer_text(one_chip, cfg, spec, 2048)
    assert asked == tiles
    assert "ragged_dot_tiling" not in text
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 2
    assert all("grouped_matmul" in ln for ln in calls)
    assert not any("cost_estimate" in ln for ln in calls)   # as the parent's


@pytest.mark.parametrize("kk,nn", [(64, 64), (64, 128), (96, 192)])
def test_grouped_matmul_compiles_at_widths_of_no_whole_lane_tile(one_chip,
                                                                  kk, nn):
    """The smoke's tiny models (`chip_smoke.py`: a hidden size of 64) run
    the kernel on the chip since PR 46: a K or an N of no whole number of
    lane tiles is one tile, taken whole; Mosaic refused the kernel's
    dynamic index into it ("cannot statically prove that index in
    dimension 1 is a multiple of 128")."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda a, b, c: pallas_ops.grouped_matmul(
        a, b, c, tiling=(64, kk, nn), reckoned=True,
        interpret=False)).lower(
        arg((16, kk), jnp.float32), arg((8, kk, nn), jnp.float32),
        arg((8,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


# the decode buckets of the four cells with an expert layer, full, half
# full and of one row, and Keye's chunk bucket of 64 tokens
DECODE_BUCKETS = [("keye", 8), ("keye", 1), ("keye", 64), ("trinity", 16),
                  ("trinity", 1), ("dsv2", 32), ("dsv2", 1), ("kimi", 32),
                  ("kimi", 64)]


@pytest.mark.parametrize("cell,rows", DECODE_BUCKETS,
                         ids=[f"{c}-{r}" for c, r in DECODE_BUCKETS])
def test_a_decode_buckets_grouped_products_take_the_repos_kernel(
        one_chip, monkeypatch, cell, rows):
    """`experts.grouped` at a decode bucket's pair rows (4 of Trinity's one
    row to Kimi-Linear's 512; no count is filled up: the kernel fills its
    rows to its tile): both products are `pallas_ops.grouped_matmul` at
    the row tile of 64 and 2 MB of an expert's matrix a tile, Mosaic
    takes them, and the compiler's grouped product, which paid every touched expert
    a visit of all the pair rows, and its expansion to a dense product
    over every group (a convolution that dilates its input by the
    groups: PR 39) are gone. The calls say what they cost."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    asked = _tiles_asked(monkeypatch)
    cfg, spec = _cell(cell)
    text = _expert_layer_text(one_chip, cfg, spec, rows)
    assert [t[0] for t in asked] == [64, 64]
    assert experts.expert_row_tile(rows * spec.experts_per_tok,
                                   spec.n_experts) == 64
    assert "ragged_dot_tiling" not in text and "lhs_dilate" not in text
    # in the instructions, not in the text's table of source files
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 2
    assert all("grouped_matmul" in ln.split(" = ")[0] for ln in calls)
    # the compiler is told what the calls cost (`reckoned`): it schedules
    # its own copies around a decode step's operations by that
    assert all("cost_estimate" in ln for ln in calls)


@pytest.mark.parametrize("c", [2048, 1024])
def test_the_latent_chunks_expanded_walk_takes_the_kernel(one_chip,
                                                          monkeypatch, c):
    """One expert layer of the DeepSeek-V2 cell's chunk (deepseek-v2-6l:
    128 heads of 128 + 64 | 128 over latents of 512, a chunk of 2,048 and
    a whole prompt of 1,024, a table of 288 blocks of 64): the expanded
    walk updates a tile in the causal kernel (a head's K filled up to 256,
    its V 128 wide), the two grouped products are the repo's kernel too, no
    score over (heads, chunk, tile) is kept, and neither pool is copied
    or converted."""
    from nnstreamer_tpu.llm import latent_moe
    from perfbench.references import latent_moe_lm
    from perfbench.runners.latent_moe_llm import lm_spec as latent_spec
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "perfbench", "configs",
                           "deepseek-v2-6l.json")) as f:
        cfg = json.load(f)
    spec = latent_spec(cfg)
    assert latent_moe.expanded_attend(c, spec)
    mb, bs, nblk, bf, i32 = 288, 64, 11000, jnp.bfloat16, jnp.int32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = jax.eval_shape(
        lambda: latent_moe_lm.make_params(cfg, 1, dtype=bf))["blocks"][1]
    blk = jax.tree.map(lambda x: arg(x.shape, x.dtype), blk)

    def layer(*a):
        return latent_moe._chunk_layer(
            *a, dense=False, tile=parts.CTX_TILE, by_block=True,
            fused=True, expanded=True, spec=spec, dtype=bf)

    compiled = jax.jit(layer, donate_argnums=(8, 9)).lower(
        blk, arg((c, 1, 5120), bf), arg((), i32), arg((c,), i32),
        arg((c,), jnp.bool_), arg((c,), i32), arg((c,), i32),
        arg((mb,), i32), arg((6, nblk, bs, 1, 512), bf),
        arg((6, nblk, bs // 2, 128), bf)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 3
    # the tile update is the causal form: no mask a query wide is built
    assert sum("causal_block_update" in ln for ln in calls) == 1
    assert not re.search(
        rf"(u32|s32|pred)\[{c},{parts.CTX_TILE}\]", text)
    # a tile's float32 scores would be heads x chunk x tile x 4 bytes
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * 128 * c * parts.CTX_TILE
    assert not re.search(
        rf"= bf16\[6,{nblk},[^ ]* (copy|convert)\(", text)


@pytest.mark.parametrize("dt,held", [(jnp.float32, 8), (jnp.bfloat16, 20)],
                         ids=["float32", "bfloat16"])
def test_the_latent_cells_grouped_products_fit_fast_memory(one_chip,
                                                           monkeypatch, dt,
                                                           held):
    """A chunk's expert layer at the DeepSeek-V2 cell's widths (12,288
    pair rows against experts of 5,120 x 3,072 and 1,536 x 5,120, a row
    tile of 256): a visit's rows and output rows, buffered twice, are 13.6
    MB in bfloat16 and 22 MB in float32, the type of the check against the
    reference, which the compiler's 16 MB a kernel refused on the chip (PR
    41) until `grouped_matmul` asked for what it needs."""
    from perfbench.runners.latent_moe_llm import lm_spec as latent_spec
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "perfbench", "configs",
                           "deepseek-v2-6l.json")) as f:
        cfg = json.load(f)
    cfg["n_routed_experts"] = held
    spec = latent_spec(cfg)
    d, f_ = cfg["hidden_size"], spec.expert_width

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = {"router": arg((d, spec.n_experts), dt),
           "ewi": arg((held, d, 2 * f_), dt), "ewd": arg((held, f_, d), dt)}
    text = jax.jit(lambda b, g, live: experts.expert_layer(
        b, g, live, spec, dt)).lower(
        blk, arg((2048, d), dt), arg((2048,), jnp.bool_)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2


def _dsv2():
    """(configuration, spec) of the DeepSeek-V2 cell."""
    from perfbench.runners.latent_moe_llm import lm_spec as latent_spec
    root = os.path.dirname(os.path.dirname(__file__))
    with open(os.path.join(root, "perfbench", "configs",
                           "deepseek-v2-6l.json")) as f:
        cfg = json.load(f)
    return cfg, latent_spec(cfg)


@pytest.mark.parametrize("dt,b,nblk", [
    (jnp.bfloat16, 32, 13438), (jnp.bfloat16, 1, 13438),
    (jnp.float32, 4, 1200)], ids=["bucket-32", "bucket-1", "float32"])
def test_the_latent_decode_walks_kernel_compiles_at_the_cells_sizes(
        one_chip, dt, b, nblk):
    """`pallas_paged.latent_decode_attn` at the DeepSeek-V2 cell's sizes
    (deepseek-v2-6l: 128 heads over latents of 512 and roped keys of 64, a
    table of 288 blocks of 64, a pool of 13,438 blocks, 6 layers, steps of
    1,024 slots): Mosaic takes it, both pools go in as they lie (a float32
    pool, the check's against the reference, keeps its unit axis: the view
    without it would copy the pool) and nothing the kernel needs is made
    outside it but the queries filled up to the packed row's width."""
    from nnstreamer_tpu.backends import pallas_paged
    from nnstreamer_tpu.llm import latent_moe

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k_pool, i_pool, li, tables, pos, n_live):
        return pallas_paged.latent_decode_attn(
            q, k_pool, i_pool, li, tables, pos, n_live, scale=0.1147,
            step=latent_moe.DECODE_STEP, interpret=False)

    i32 = jnp.int32
    compiled = jax.jit(attend).lower(
        arg((b, 128, 576), dt), arg((6, nblk, 64, 1, 512), dt),
        arg((6, nblk, 32, 128), dt), arg((), i32), arg((b, 288), i32),
        arg((b,), i32), arg((), i32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 1 and "latent_decode_attn" in calls[0]
    assert not re.search(rf"\[6,{nblk},[^ ]* (copy|convert|fusion)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("b", [32, 1])
def test_the_latent_decode_layer_reads_the_pools_where_they_lie(
        one_chip, monkeypatch, b):
    """One expert layer of the DeepSeek-V2 cell's decode step under the
    fused walk: the text holds no array of a layer's pool shape (a
    ``k_pool[li]`` in front of the kernel would be one: 880 MB a layer)
    and none of the pools' own shape but the pools, their views without
    the unit axis and the step's writes into them, in place."""
    from nnstreamer_tpu.backends import pallas_paged
    from nnstreamer_tpu.llm import latent_moe
    from perfbench.references import latent_moe_lm
    cfg, spec = _dsv2()
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        assert latent_moe.fused_decode(64, spec, jnp.bfloat16)
    monkeypatch.setattr(pallas_paged, "_interpret", lambda: False)
    # a bucket of 32 rows takes its grouped products through the kernel
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    mb, bs, nblk, bf, i32 = 288, 64, 13438, jnp.bfloat16, jnp.int32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = jax.eval_shape(
        lambda: latent_moe_lm.make_params(cfg, 1, dtype=bf))["blocks"][1]
    blk = jax.tree.map(lambda x: arg(x.shape, x.dtype), blk)

    def layer(*a):
        return latent_moe._decode_layer(*a, dense=False, t=0, spec=spec,
                                        dtype=bf)

    compiled = jax.jit(layer, donate_argnums=(8, 9)).lower(
        blk, arg((b, 1, 5120), bf), arg((), i32), arg((b,), i32),
        arg((b,), jnp.bool_), arg((b,), i32), arg((b,), i32),
        (arg((b, mb), i32), arg((), i32)),
        arg((6, nblk, bs, 1, 512), bf), arg((6, nblk, bs // 2, 128), bf)
    ).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert sum("latent_decode_attn" in ln for ln in calls) == 1
    # the plain walk's loop is gone, whose carry was every row's sums
    assert not any(f"f32[{b},128,512]" in ln for ln in text.splitlines()
                   if " while(" in ln)
    assert not re.search(rf"bf16\[(1,)?{nblk},", text)
    made = set(re.findall(rf"= bf16\[6,{nblk},\S* ([a-z-]+)\(", text))
    assert made <= {"parameter", "bitcast", "scatter", "fusion",
                    "get-tuple-element", "dynamic-update-slice", "while"}, \
        made
    fusions = [ln for ln in text.splitlines()
               if re.search(rf"= bf16\[6,{nblk},[^ ]* fusion\(", ln)]
    assert all("scatter" in ln for ln in fusions)
    # both pools are written in place: nothing a pool wide is kept beside
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# -- the delta family's cell (kimi-linear-48b-a3b-8l) -----------------------------

def _kimi():
    from perfbench.runners import delta_moe_llm
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "perfbench",
                           "configs", "kimi-linear-48b-a3b-8l.json")) as f:
        cfg = json.load(f)
    return cfg, delta_moe_llm.lm_spec(cfg)


def _kimi_layer(cfg, one_chip, layer: int):
    """The shapes of published layer `layer` + 1's weights, on the chip."""
    from perfbench.references import delta_moe_lm
    blk = jax.eval_shape(lambda: delta_moe_lm.make_params(
        cfg, 1, dtype=jnp.bfloat16))["blocks"][layer]
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), blk)


@pytest.mark.parametrize("b", [64, 1])
def test_the_kda_decode_layer_updates_states_and_tails_in_place(
        one_chip, monkeypatch, b):
    """One KDA expert layer of the Kimi cell's decode step: the rule takes
    the kernel at the cell's widths on the chip, and the rows' states go
    through their slots inside it (`pallas_state.delta_decode_update`):
    both by-slot pools are aliased to the outputs, the program keeps less
    beside them than one gathered copy of 64 rows' states (134 MB), the
    text holds no array of the gathered states' shape and neither a
    scatter nor a `dynamic-update-slice` into the state pool, which stays
    float32 and is made by nothing but the kernel."""
    from nnstreamer_tpu.backends import pallas_state
    from nnstreamer_tpu.llm import delta_moe
    cfg, spec = _kimi()
    bf, i32, slots = jnp.bfloat16, jnp.int32, 65
    # on the chip: the rule says so, and no kernel is interpreted (a bucket
    # of 64 rows takes its grouped products through the repo's too)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_moe.fused_state(spec)
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_state, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(*a):
        return delta_moe._decode_kda(*a, dense=False, spec=spec, dtype=bf)

    tails, states = (6, slots, 1, 9 * 4096), (6, slots, 32, 128, 128)
    compiled = jax.jit(layer, donate_argnums=(5, 6)).lower(
        _kimi_layer(cfg, one_chip, 1), arg((b, 1, 2304), bf), arg((), i32),
        arg((b,), jnp.bool_), arg((b,), i32), arg(tails, bf),
        arg(states, jnp.float32)).compile()
    mem = compiled.memory_analysis()
    pools = 6 * slots * (3 * 12288 * 2 + 32 * 128 * 128 * 4)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 64 * 32 * 128 * 128 * 4
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert sum("delta_decode_update" in ln for ln in calls) == 1
    assert not re.search(rf"f32\[{b},32,128,128\]", text)
    pool = rf"f32\[6,{slots},32,128,128\]"
    assert not re.search(rf"bf16\[6,{slots},32,128,128\]", text)
    made = set(re.findall(rf"= {pool}\S* ([a-z-]+)\(", text))
    assert made <= {"parameter", "get-tuple-element", "bitcast"}, made
    assert not any(re.search(pool, ln) for ln in text.splitlines()
                   if " scatter(" in ln or " dynamic-update-slice(" in ln)


@pytest.mark.parametrize("c", [2048, 256])
def test_the_kda_chunk_layer_fits_beside_the_pools(one_chip, monkeypatch, c):
    """One KDA expert layer of the Kimi cell's chunk: the closed form over
    runs of 64 at `highest`, the systems solved for all runs at once; its
    temporaries (the chunk's float32 q, k, v, g, the runs' A, B and T, a
    run's pairwise decays) stay under 1 GB, the pools written in place."""
    from nnstreamer_tpu.llm import delta_moe
    cfg, spec = _kimi()
    bf, i32, slots = jnp.bfloat16, jnp.int32, 65
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(*a):
        return delta_moe._chunk_kda(*a, dense=False, run=delta_moe.RUN,
                                    spec=spec, dtype=bf)

    compiled = jax.jit(layer, donate_argnums=(7, 8)).lower(
        _kimi_layer(cfg, one_chip, 1), arg((c, 1, 2304), bf), arg((), i32),
        arg((c,), jnp.bool_), arg((), i32), arg((), jnp.bool_),
        arg((), i32), arg((6, slots, 1, 9 * 4096), bf),
        arg((6, slots, 32, 128, 128), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * slots * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 30
    # the grouped products of 8 x c pair rows: the repo's kernel past 512
    assert ("grouped_matmul" in compiled.as_text()) == (8 * c > 512)


@pytest.mark.parametrize("what", ["decode-64", "chunk-2048", "chunk-256"])
def test_the_kimi_cells_latent_layers_take_the_latent_familys_kernels(
        one_chip, monkeypatch, what):
    """A latent expert layer of the Kimi cell (32 heads of 128 + 64 | 128
    over latents of 512, no query rank, nothing turned; a pool of its 2
    latent layers) through `latent_moe.decode_layer` / `chunk_layer`: the
    decode walk's kernel and the expanded chunk's causal tile update
    compile at this second shape, the query goes through one matrix, and
    neither pool is copied or converted."""
    from nnstreamer_tpu.backends import pallas_paged
    from nnstreamer_tpu.llm import latent_moe
    cfg, spec = _kimi()
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_paged, "_interpret", lambda: False)
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        assert latent_moe.fused_decode(64, spec, jnp.bfloat16)
    mb, bs, nblk, bf, i32 = 288, 64, 39000, jnp.bfloat16, jnp.int32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = _kimi_layer(cfg, one_chip, 3)              # published layer 4
    assert "wq" in blk and "wqa" not in blk
    pools = (arg((2, nblk, bs, 1, 512), bf), arg((2, nblk, bs // 2, 128), bf))
    if what == "decode-64":
        b = 64

        def layer(*a):
            return latent_moe.decode_layer(*a, dense=False, t=0, spec=spec,
                                           dtype=bf)

        compiled = jax.jit(layer, donate_argnums=(8, 9)).lower(
            blk, arg((b, 1, 2304), bf), arg((), i32), arg((b,), i32),
            arg((b,), jnp.bool_), arg((b,), i32), arg((b,), i32),
            (arg((b, mb), i32), arg((), i32)), *pools).compile()
        kernel, limit = "latent_decode_attn", 256 << 20
    else:
        c = int(what.split("-")[1])
        assert latent_moe.expanded_attend(c, spec)

        def layer(*a):
            return latent_moe.chunk_layer(
                *a, dense=False, tile=parts.CTX_TILE, by_block=True,
                fused=True, expanded=True, spec=spec, dtype=bf)

        compiled = jax.jit(layer, donate_argnums=(8, 9)).lower(
            blk, arg((c, 1, 2304), bf), arg((), i32), arg((c,), i32),
            arg((c,), jnp.bool_), arg((c,), i32), arg((c,), i32),
            arg((mb,), i32), *pools).compile()
        # a tile's float32 scores would be heads x chunk x tile x 4 bytes
        kernel, limit = "causal_block_update", 4 * 32 * c * parts.CTX_TILE
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert sum(kernel in ln for ln in calls) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    assert not re.search(
        rf"= bf16\[2,{nblk},[^ ]* (copy|convert)\(", text)
