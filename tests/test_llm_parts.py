"""What the family modules share (llm/parts.py, llm/experts.py) and the
rule that keeps them a layer: no family module imports another, and no
module of the serving path uses another module's private name. On the
CPU; the kernels' side of the walk is `tests/test_sparse_moe_attend.py`'s.
"""

import ast
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.llm import parts
from nnstreamer_tpu.models.transformer import rmsnorm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LLM = os.path.join(ROOT, "nnstreamer_tpu", "llm")
FAMILIES = ("paged_model", "sparse_moe", "hybrid_lm", "window_moe",
            "latent_moe", "delta_moe")
# the one arrow between two of them: the delta family's latent layers are
# the latent family's whole layers, under its public names
STANDS_ON = {"delta_moe": {"latent_moe"}}
# the serving path from the element's executor down, and the smoke that
# drives it on the chip
HELD = sorted(os.path.relpath(p, ROOT)
              for p in glob.glob(os.path.join(LLM, "*.py"))) + [
    os.path.join("nnstreamer_tpu", "backends", "llm_exec.py"),
    "chip_smoke.py"]


# -- the rule -------------------------------------------------------------------

def _imports(tree):
    """(line, dotted name, the name it is bound to) of every module or
    name a file imports."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name, a.asname or a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module != "__future__":
            for a in node.names:
                yield (node.lineno, f"{node.module}.{a.name}",
                       a.asname or a.name)


def _private(name: str) -> bool:
    return name.startswith("_") and not (
        name.startswith("__") and name.endswith("__"))


@pytest.mark.parametrize("family", FAMILIES)
def test_no_family_module_imports_another(family):
    with open(os.path.join(LLM, family + ".py")) as f:
        tree = ast.parse(f.read())
    others = {f"nnstreamer_tpu.llm.{m}" for m in FAMILIES
              if m != family and m not in STANDS_ON.get(family, ())}
    found = [(line, name) for line, name, _ in _imports(tree)
             if any(name == o or name.startswith(o + ".") for o in others)]
    assert not found, (
        f"llm/{family}.py imports another family module: {found}; what two "
        f"families use lives in llm/parts.py or llm/experts.py")


@pytest.mark.parametrize("path", HELD)
def test_no_private_name_of_another_module(path):
    """Neither ``from m import _x`` (or a module ``_m``) nor ``m._x`` for
    a name `m` the file imported."""
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read())
    imported = list(_imports(tree))
    found = [(line, name) for line, name, _ in imported
             if any(_private(part) for part in name.split("."))]
    bound = {alias for _, _, alias in imported}
    found += [(n.lineno, f"{n.value.id}.{n.attr}") for n in ast.walk(tree)
              if isinstance(n, ast.Attribute) and _private(n.attr)
              and isinstance(n.value, ast.Name) and n.value.id in bound]
    assert not found, f"{path} uses another module's private name: {found}"


# -- the walk over a chunk's context tiles ------------------------------------

TILE, BS, MB, NB = 16, 4, 13, 40        # a table of 52 slots: 4 tiles, padded


def _reference(q, qpos, k, v, window):
    """Plain float32 masked softmax over the whole gathered context:
    q (C, Hkv, G, hd), k (S, Hkv, hd), v (S, Hkv, vw)."""
    s = jnp.einsum("cgrd,sgd->grcs", q, k, precision="highest") \
        * q.shape[-1] ** -0.5
    slot = jnp.arange(k.shape[0])[None, :]
    on = slot <= qpos[:, None]
    if window:
        on = on & (slot > qpos[:, None] - window)
    p = jax.nn.softmax(jnp.where(on[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grcs,sgd->grcd", p, v, precision="highest")


@pytest.mark.parametrize("pos0,c,window,nkv,grp,hd,vw,span", [
    (30, 8, 0, 2, 3, 8, 8, (0, 3)),
    (4, 8, 4, 2, 3, 8, 8, (0, 1)),
    (26, 8, 20, 2, 3, 8, 8, (0, 3)),
    (40, 8, 6, 2, 3, 8, 8, (2, 3)),
    (20, 8, 0, 1, 5, 8, 4, (0, 2))],
    ids=["causal", "window-in-a-tile", "window-across-tiles",
         "first-tile-not-0", "values-narrower"])
def test_walk_tiles_is_the_masked_softmax(pos0, c, window, nkv, grp, hd, vw,
                                          span):
    rng = np.random.default_rng(pos0 + window)
    table = jnp.asarray(1 + rng.permutation(NB - 1)[:MB], jnp.int32)
    k_pool = jnp.asarray(rng.normal(size=(NB, BS, nkv, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(NB, BS, nkv, vw)), jnp.float32)
    qg = jnp.asarray(rng.normal(size=(c, nkv, grp, hd)), jnp.float32)
    qpos = pos0 + jnp.arange(c)
    assert parts.tile_span(pos0, c, MB * BS, TILE, window) == span

    def walk(pos0):
        tab = parts.whole_tiles(table, TILE, BS)
        assert tab.shape == (16,)

        def read(bl):
            return (k_pool[bl].reshape(TILE, nkv, hd),
                    v_pool[bl].reshape(TILE, nkv, vw))

        def update(j, kt, vt, state):
            return parts.causal_update(
                qg, None, kt, vt, pos0 + jnp.arange(c), j * TILE, state,
                window=window, fused=False)

        return parts.walk_tiles(
            tab, parts.tile_span(pos0, c, MB * BS, TILE, window),
            TILE // BS, read, update, (nkv, grp), c, vw, l_floor=1e-30)

    got = jax.jit(walk)(jnp.int32(pos0))          # the span traced
    want = _reference(qg, qpos, k_pool[table].reshape(-1, nkv, hd),
                      v_pool[table].reshape(-1, nkv, vw), window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_whole_tiles_refuses_a_block_that_does_not_divide_the_tile():
    with pytest.raises(ValueError, match="does not divide the context tile"):
        parts.whole_tiles(jnp.zeros((4,), jnp.int32), 16, 6)


@pytest.mark.parametrize("pos0,c,slots", [
    (0, 8, 64), (8, 8, 64), (9, 8, 64), (56, 8, 64), (60, 16, 64),
    (0, 64, 52), (40, 16, 52)])
def test_tile_span_is_the_three_trip_counts_it_replaced(pos0, c, slots):
    """The sparse-expert program's inline count, the hybrid family's
    `live_tiles` and the host's copy in `SparseMoESet.note_chunk`."""
    tile = 16
    cap = -(-slots // tile)
    inline = min((pos0 + c + tile - 1) // tile, cap)
    n = -(-(pos0 + c) // tile)
    live_tiles = n - (n > cap) * (n - cap)
    first, end = parts.tile_span(pos0, c, slots, tile)
    assert (first, end) == (0, inline) == (0, live_tiles)
    traced = jax.jit(lambda p: parts.tile_span(p, c, slots, tile))(
        jnp.int32(pos0))
    assert [int(x) for x in traced] == [0, inline]


# -- one finish, one write_chunk, against the callers' old forms -------------

def _proj(params, x):
    return (x @ params["head"]).astype(jnp.float32)


OLD_FINISH = {
    # sparse_moe._finish(params, x, dtype)
    "sparse_moe": (lambda p, x: _proj(p, rmsnorm(x, p["ln_f"])), {}),
    # hybrid_lm._finish: the final norm's output over logit_div
    "hybrid": (lambda p, x: _proj(
        p, (rmsnorm(x, p["ln_f"]) / 3.5).astype(x.dtype)),
        {"logit_div": 3.5}),
    # window_moe._finish and latent_moe._finish: the spec's norm_eps
    "window_moe": (lambda p, x: _proj(p, rmsnorm(x, p["ln_f"], 1e-5)),
                   {"eps": 1e-5}),
    "latent_moe": (lambda p, x: _proj(p, rmsnorm(x, p["ln_f"], 1e-3)),
                   {"eps": 1e-3}),
}


@pytest.mark.parametrize("family", sorted(OLD_FINISH))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_finish_is_each_callers_old_form(family, dtype):
    rng = np.random.default_rng(7)
    params = {"ln_f": jnp.asarray(1 + 0.1 * rng.normal(size=(32,)), dtype),
              "head": jnp.asarray(rng.normal(size=(32, 50)), dtype)}
    x = jnp.asarray(rng.normal(size=(5, 32)), dtype)
    old, kw = OLD_FINISH[family]
    got = parts.finish(params, x, dtype, **kw)
    assert got.dtype == jnp.float32 and got.shape == (5, 50)
    assert jnp.array_equal(got, old(params, x))


def _old_sparse_write(pools, li, blk_idx, blk_off, k, v, ki, by_block):
    """sparse_moe._write_chunk as it was: three pools at once."""
    k_pool, v_pool, i_pool = pools
    bs = k_pool.shape[2]
    if not by_block:
        return (k_pool.at[li, blk_idx, blk_off].set(k.astype(k_pool.dtype)),
                v_pool.at[li, blk_idx, blk_off].set(v.astype(v_pool.dtype)),
                parts.idx_write(i_pool, li, blk_idx, blk_off, ki))
    first = blk_idx.reshape(k.shape[0] // bs, bs)[:, 0]
    return (parts.put_blocks(k_pool, li, first, k),
            parts.put_blocks(v_pool, li, first, v),
            parts.put_blocks(i_pool, li, first, ki))


def _old_window_write(pool, li, blk_idx, blk_off, x, by_block):
    """window_moe._write_chunk as it was: one pool of heads."""
    if not by_block:
        return pool.at[li, blk_idx, blk_off].set(x.astype(pool.dtype))
    bs = pool.shape[2]
    first = blk_idx.reshape(x.shape[0] // bs, bs)[:, 0]
    return parts.put_blocks(pool, li, first, x)


def _old_latent_write(pools, li, blk_idx, blk_off, lat, k_pe, by_block):
    """latent_moe._chunk_layer's writes as they were: the latents through
    the window family's, the packed roped keys inline."""
    k_pool, i_pool = pools
    c, bs = lat.shape[0], k_pool.shape[2]
    k_pool = _old_window_write(k_pool, li, blk_idx, blk_off,
                               lat[:, None, :], by_block)
    if by_block:
        return k_pool, parts.put_blocks(
            i_pool, li, blk_idx.reshape(c // bs, bs)[:, 0], k_pe)
    return k_pool, parts.idx_write(i_pool, li, blk_idx, blk_off, k_pe)


@pytest.mark.parametrize("by_block", [True, False], ids=["block", "row"])
@pytest.mark.parametrize("family", ["sparse_moe", "window_moe",
                                    "latent_moe"])
def test_write_chunk_is_each_callers_old_form(family, by_block):
    rng = np.random.default_rng(3)
    layers, nb, bs, c, li = 2, 12, 8, 16, 1
    blocks = 1 + rng.permutation(nb - 1)[:c // bs]
    blk_idx = jnp.asarray(np.repeat(blocks, bs), jnp.int32)
    blk_off = jnp.asarray(np.tile(np.arange(bs), c // bs), jnp.int32)

    def pool(*shape):
        return jnp.asarray(rng.normal(size=(layers, nb) + shape),
                           jnp.bfloat16)

    def rows(*shape):
        return jnp.asarray(rng.normal(size=(c,) + shape), jnp.float32)

    def write(p, x):
        return parts.write_chunk(p, li, blk_idx, blk_off, x, by_block)

    if family == "sparse_moe":          # K, V and packed indexer keys
        pools = pool(bs, 2, 4), pool(bs, 2, 4), pool(bs // 2, 2 * 6)
        xs = rows(2, 4), rows(2, 4), rows(6)
        want = _old_sparse_write(pools, li, blk_idx, blk_off, *xs, by_block)
    elif family == "window_moe":        # K (and V alike) of a kind
        pools, xs = (pool(bs, 2, 4),), (rows(2, 4),)
        want = (_old_window_write(pools[0], li, blk_idx, blk_off, xs[0],
                                  by_block),)
    else:                               # latents and packed roped keys
        pools = pool(bs, 1, 16), pool(bs // 4, 4 * 2)
        lat, k_pe = rows(16), rows(2)
        xs = lat[:, None, :], k_pe
        want = _old_latent_write(pools, li, blk_idx, blk_off, lat, k_pe,
                                 by_block)
    got = [write(p, x) for p, x in zip(pools, xs)]
    for g, w, p in zip(got, want, pools):
        assert g.dtype == p.dtype and jnp.array_equal(g, w)
        assert not jnp.array_equal(g, p)            # something was written
        assert jnp.array_equal(g[0], p[0])          # and only in layer `li`


def test_layer_index_counts_a_layer_among_its_kind():
    assert parts.layer_index(("window", "window", "full", "window",
                              "full")) == [0, 1, 0, 2, 1]
    assert parts.layer_index(()) == []
