"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU host

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the models the repo supports, with seeded random
weights, frames and prompts:

- ``kernels``      every Pallas kernel compiled by Mosaic (never interpret
                   mode) at the shapes the pipelines feed it, checked against
                   a plain-XLA reference, ``tpu_custom_call`` in its lowering;
- ``label_host``   the README label pipeline (MobileNetV2 1.0 at 224²) through
  ``label_device`` ``parse_launch`` + ``PipelineRunner``, host decode and
                   ``device=true`` decode, long enough for the compiled
                   steady-state window to arm and run; classes agree with a
                   direct ``jax.jit`` of the model on the same frames;
- ``llm_xla``      ``appsrc ! tensor_llm ! tensor_sink`` on a store-registered
  ``llm_pallas``   transformer (d_model 1024, 8 heads × 128, 4 layers, vocab
                   512, bf16), ``paged_kernel=xla`` then ``pallas``: every
                   request answered, identical greedy tokens, the pallas arm's
                   invokes all counted under ``pallas``;
- ``llm_sparse_moe`` the sparse-expert family (llm/sparse_moe.py) at a tiny
                   size (hidden 64, 8 q / 2 kv heads of 16, 8 experts top-2,
                   indexer 2 x 8 top-8, float32): chunked prefill and decode
                   through the same element on the chip serve the tokens the
                   same engine serves on this host's CPU device;
- ``llm_hybrid``   the hybrid family (llm/hybrid_lm.py) at a tiny size (hidden
                   64, two sparse layers of 4 q / 2 kv heads of 16 attending
                   3 blocks of 8 tokens, two linear layers of 4 heads with a
                   carried state, float32): chunked prefill and decode over
                   blocks and state slots through the same element on the
                   chip serve the tokens the same engine serves on this
                   host's CPU device;
- ``llm_window_moe`` the window family (llm/window_moe.py) at a tiny size
                   (hidden 64, a window layer, a full layer and a window
                   layer of 8 q / 2 kv heads of 16, a window of 8 over
                   blocks of 4, the first layer dense, then a shared expert
                   beside 4 held of 8 routed experts top-2 with sigmoid
                   scores, float32): chunked prefill and decode over two
                   tables a sequence through the same element on the chip
                   serve the tokens the same engine serves on this host's
                   CPU device, with window blocks given back and regranted;
- ``llm_latent_moe`` the latent family (llm/latent_moe.py) at a tiny size
                   (hidden 64, 4 heads of 8 + 4 | 8 through ranks 24 and 16,
                   YaRN, blocks of 4, the first layer dense, then shared
                   experts beside 2 held of 16 routed experts, 3 of 8 groups
                   and 4 a token, float32): chunked prefill and decode over
                   the pool of latents and roped keys through the same
                   element on the chip serve the tokens the same engine
                   serves on this host's CPU device, once with chunks of 8
                   (the absorbed form) and once with chunks of 32 (the
                   expanded form);
- ``llm_delta_moe`` the delta family (llm/delta_moe.py) at a tiny size
                   (hidden 64, layers K K L K L: KDA of 2 heads of 8 with a
                   convolution over 4 tokens, latent attention of 4 heads of
                   8 + 4 | 8 through a latent of 16 with no query rank and
                   no rope, blocks of 4, the first layer dense, then a
                   shared expert beside 4 held of 16 routed experts, 4 a
                   token, sigmoid scores, float32): chunked prefill (runs of
                   the closed form, states and tails handed chunk to chunk
                   by slot) and decode over both kinds of cache through the
                   same element on the chip serve the tokens the same engine
                   serves on this host's CPU device, once with chunks of 8
                   and once with chunks of 32 (runs of 16, the latent
                   layers' expanded form), the rows' states gathered by
                   slot; and once with KDA heads of 128, where the decode
                   step moves a row's state through its slot in one kernel
                   (`pallas_state.delta_decode_update`);
- ``multichip``    with four or more devices: ``tensor_filter devices=4`` on
                   four distinct chips, ``tensor_llm shards=4`` equal to
                   ``shards=1``, ring prefill through the Pallas block kernel.

There is no CPU mode, no interpret mode and no smaller size off the chip: the
script exits non-zero at once unless ``jax.devices()[0].platform == "tpu"``.
It exits non-zero if any leg failed, and only a full pass prints the last
line, one JSON object ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import sys
import time
import traceback

N_FRAMES = 48               # label legs: enough for the window to arm twice
FRAME_SEED = 7
NORMALIZE = "typecast:float32,add:-127.5,div:127.5"
LLM = dict(d_model=1024, n_heads=8, n_layers=4, vocab=512)   # bench.py's
LLM_REQUESTS = 4                                             # widest
LLM_NEW_TOKENS = 8


def _bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 (8 significand bits) at magnitude `x`."""
    import math

    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


# -- kernels -----------------------------------------------------------------

def leg_kernels() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from nnstreamer_tpu.backends import pallas_ops as po
    from nnstreamer_tpu.backends import pallas_paged as pp
    from nnstreamer_tpu.parallel.ring_attention import ring_attention

    assert jax.default_backend() == "tpu", \
        "Pallas kernels would run in interpret mode"
    rng = np.random.default_rng(0)
    errs = {}

    def check(name, fn, args, ref, tol):
        """Compile `fn` for the chip, require a Mosaic custom call in its
        lowering, run it, compare every output with `ref(*args)`."""
        jitted = jax.jit(fn)
        assert "tpu_custom_call" in jitted.lower(*args).as_text(), \
            f"{name}: no tpu_custom_call in the lowering"
        got = [np.asarray(g, np.float32)
               for g in jax.tree_util.tree_leaves(jitted(*args))]
        want = [np.asarray(w, np.float32)
                for w in jax.tree_util.tree_leaves(ref(*args))]
        assert len(got) == len(want), name
        err = 0.0
        for g, w in zip(got, want):
            assert g.shape == w.shape, (name, g.shape, w.shape)
            assert np.isfinite(g).all(), f"{name}: non-finite output"
            err = max(err, float(np.abs(g - w).max()))
        assert err <= tol, f"{name}: max abs err {err} > {tol}"
        errs[name] = err

    def normal(shape, dtype=jnp.float32):
        # bf16-exact values: the MXU's default-precision pass rounds its
        # operands to bf16, so these inputs lose nothing on the way in
        return jnp.asarray(rng.normal(0, 1, shape),
                           jnp.bfloat16).astype(dtype)

    # ingest kernels at the frame shape the pipeline feeds them (4-D)
    frame = rng.integers(0, 256, (1, 224, 224, 3), np.uint8)
    check("normalize_u8", po.normalize_u8, (frame,),
          lambda x: (x.astype(np.float32) - 127.5) / 127.5, 1e-6)
    xf = rng.normal(0, 2, (1, 224, 224, 3)).astype(np.float32)
    check("clamp_scale", lambda a: po.clamp_scale(a, -1.0, 1.0, 2.0, 0.5),
          (xf,), lambda a: np.clip(a, -1, 1) * 2 + 0.5, 1e-6)
    for m in (16, 4):                       # 4: the zero-padded-rows path
        check(f"quantize_rows_m{m}", po.quantize_rows,
              (normal((m, 1024), jnp.bfloat16),), po.quantize_rows_xla, 1.0)

    def attn_ref(q, k, v, causal, q0=0):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32),
                       precision="highest") * q.shape[-1] ** -0.5
        if causal:
            rows = q0 + jnp.arange(s.shape[-2])[:, None]
            s = jnp.where(rows >= jnp.arange(s.shape[-1])[None, :],
                          s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          v.astype(jnp.float32), precision="highest")

    q, k, v = (normal((1, 2048, 8, 128), jnp.bfloat16) for _ in range(3))
    for causal in (False, True):
        check(f"flash_attention_causal{int(causal)}",
              lambda q, k, v, c=causal: po.flash_attention(q, k, v, causal=c),
              (q, k, v), lambda q, k, v, c=causal: attn_ref(q, k, v, c), 3e-2)
    # the K-grid streaming path (a head's K+V past the VMEM budget);
    # checked on the last 128 query rows
    q, k, v = (normal((1, 32768, 1, 128), jnp.bfloat16) for _ in range(3))
    check("flash_attention_kgrid",
          lambda q, k, v: po.flash_attention(q, k, v, causal=True)[:, -128:],
          (q, k, v),
          lambda q, k, v: attn_ref(q[:, -128:], k, v, True, q0=32768 - 128),
          3e-2)
    # ring attention picks the Pallas block kernel by itself on a TPU
    # backend: flash_block_update inside shard_map (one-device ring here;
    # the multichip leg rotates it over four)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v = (normal((1, 256, 8, 128)) for _ in range(3))
    check("ring_attention_block_kernel",
          lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True),
          (q, k, v), lambda q, k, v: attn_ref(q, k, v, True), 3e-2)

    # the sparse-expert chunk's fused tile update at the Keye cell's head
    # geometry (8 query heads a KV head of 128), against its plain twin:
    # tile 1 of 2, a tie at every fourth slot cut at a query's own place,
    # a carry that arrives filled
    from nnstreamer_tpu.llm import experts, parts

    c, tile, nkv, grp = 512, 1024, 2, 8
    keys = jnp.asarray(rng.integers(1, 9, (c, 2 * tile)), jnp.uint32) << 28
    keys = keys.at[:, ::4].set(jnp.uint32(5 << 28))
    t = jnp.full((c,), 5 << 28, jnp.uint32)
    cut = jnp.asarray(tile + rng.integers(-8, tile + 8, (c,)), jnp.int32)
    carry = (normal((nkv, grp, c)), 1.0 + jnp.abs(normal((nkv, grp, c))),
             normal((nkv, grp, c, 128)))
    check("selected_block_update",
          lambda qg, kt, vt, m, l, a: po.selected_block_update(
              qg.transpose(1, 2, 0, 3), kt, vt, keys, t, cut, 1, m, l, a),
          (normal((c, nkv, grp, 128), jnp.bfloat16),
           normal((tile, nkv, 128), jnp.bfloat16),
           normal((tile, nkv, 128), jnp.bfloat16), *carry),
          lambda qg, kt, vt, m, l, a: parts.attend_plain(
              qg, kt, vt, keys[:, tile:], t, cut, tile, (m, l, a)), 3e-2)

    # the hybrid chunk's walk at the SALA cell's head geometry (2 KV heads
    # of 16 query heads of 128, blocks of 64 tokens, a pool block 16): the
    # same kernel a call a KV head, against the walk by the plain update;
    # queries across the second tile's first slots, every fourth block on
    from nnstreamer_tpu.llm import hybrid_lm
    from nnstreamer_tpu.llm.spec import HYBRID, LMSpec

    hy = LMSpec(family=HYBRID, n_heads=32, n_kv=2, head_dim=128,
                sel_block=64)
    c, blocks = 256, 2 * tile // 16
    tab = jnp.asarray(1 + rng.permutation(255)[:blocks], jnp.int32)
    qpos = jnp.arange(tile - 128, tile - 128 + c)
    on = jnp.asarray(rng.integers(0, 4, (c, 2, 2 * tile // 64)) == 0)
    on = on.at[:, :, 0].set(True)
    check("hybrid_walk_fused",
          lambda q, kp, vp: hybrid_lm.sparse_attend_walk(
              q, qpos, on, tab, 2, 1, kp, vp, spec=hy, dtype=jnp.bfloat16,
              fused=True, tile=tile),
          (normal((c, 32, 128), jnp.bfloat16),
           normal((4, 256, 16, 1, 128), jnp.bfloat16),
           normal((4, 256, 16, 1, 128), jnp.bfloat16)),
          lambda q, kp, vp: hybrid_lm.sparse_attend_walk(
              q, qpos, on, tab, 2, 1, kp, vp, spec=hy, dtype=jnp.bfloat16,
              fused=False, tile=tile), 3e-2)

    # the window family's chunk walk at the Trinity cell's head geometry
    # (8 KV heads of 6 query heads of 128, pool blocks of 64): the tile
    # update's causal form (`pallas_ops.causal_block_update`: the mask from
    # positions inside the kernel, 128 queries a program) behind the causal
    # edge and a window of 1,536, tiles 1 to 3 of a table of 4 (blocks that
    # see a tile whole, in part and not at all), against the walk by the
    # plain update
    from nnstreamer_tpu.llm import window_moe

    c = 512
    tab = jnp.asarray(1 + rng.permutation(95)[:4 * tile // 64], jnp.int32)
    qpos = jnp.arange(3 * tile - c, 3 * tile)
    span = parts.tile_span(3 * tile - c, c, 4 * tile, tile, 1536)
    assert span == (1, 3), span
    check("window_walk_fused",
          lambda q, kp, vp: window_moe.attend_tiles(
              q, qpos, tab, span, 1, kp, vp, window=1536, fused=True,
              tile=tile, dtype=jnp.bfloat16),
          (normal((c, 48, 128), jnp.bfloat16),
           normal((2, 96, 64, 8, 128), jnp.bfloat16),
           normal((2, 96, 64, 8, 128), jnp.bfloat16)),
          lambda q, kp, vp: window_moe.attend_tiles(
              q, qpos, tab, span, 1, kp, vp, window=1536, fused=False,
              tile=tile, dtype=jnp.bfloat16), 3e-2)

    # the latent family's chunk walk at the published head widths (16
    # heads of 128 + 64 | 128 over latents of 512 and roped keys of 64, two
    # a row, pool blocks of 64): the expanded form through the causal
    # kernel (a head's K filled up to 256, its V 128 wide, a head a group
    # of one: 512 queries a program) against the absorbed form through the
    # plain update, tiles 0 to 2 of a table of 4
    from nnstreamer_tpu.llm import latent_moe

    la = LMSpec(family="latent_moe", n_heads=16, q_rank=256, kv_rank=512,
                nope_dim=128, rope_dim=64, v_dim=128, yarn_factor=40.0,
                yarn_orig_len=4096, yarn_mscale=0.707,
                yarn_mscale_all_dim=0.707)
    span = (0, 3)
    wkvb = normal((512, 16, 256), jnp.bfloat16) * 0.05

    def latent_walk(expanded, fused):
        return lambda qn, qp, kp, ip: latent_moe.attend_tiles(
            qn, qp, qpos, tab, span, 1, kp, ip, wkvb, expanded=expanded,
            fused=fused, tile=tile, spec=la, dtype=jnp.bfloat16)

    check("latent_walk_fused", latent_walk(True, True),
          (normal((c, 16, 128), jnp.bfloat16),
           normal((c, 16, 64), jnp.bfloat16),
           normal((2, 96, 64, 1, 512), jnp.bfloat16),
           normal((2, 96, 32, 128), jnp.bfloat16)),
          latent_walk(False, False), 3e-2)

    # the same family's decode walk as one kernel a row (the pools whole
    # and in place, read through the tables; a bucket of 4 of which 3 rows
    # are live, at position 0, on a step's last slot and 3 steps deep)
    # against the plain walk's work list, layer 1 of 2
    from nnstreamer_tpu.backends import pallas_paged

    assert latent_moe.fused_decode(64, la, jnp.bfloat16)
    dpos = jnp.asarray([0, 1023, 2100, 0], jnp.int32)
    dtab = np.zeros((4, 48), np.int32)
    dtab[0, :1], dtab[1, :16], dtab[2, :33] = 1, np.arange(2, 18), \
        18 + rng.permutation(60)[:33]
    dtab = jnp.asarray(dtab)
    walk = latent_moe.walk_plan(64, 4, 48)

    def decode_plain(q, kp, ip):
        items = parts.live_items(dtab, dpos, 64, *walk)
        return latent_moe.attend_latent(q, kp, ip, 1, items, walk[2],
                                        0.05)[:3]

    check("latent_decode_fused",
          lambda q, kp, ip: pallas_paged.latent_decode_attn(
              q, kp, ip, jnp.int32(1), dtab, dpos, jnp.int32(3), scale=0.05,
              step=latent_moe.DECODE_STEP)[:3],
          (normal((4, 16, 576), jnp.bfloat16),
           normal((2, 96, 64, 1, 512), jnp.bfloat16),
           normal((2, 96, 32, 128), jnp.bfloat16)), decode_plain, 3e-2)

    # the expert layer through the grouped-product kernel, a chunk's (1,024
    # tokens x 4 of 64 experts, 8 held: 4,096 pair rows of which an
    # eighth is held, a row tile of 128) and a decode bucket's (64 rows x
    # 4: 256 pair rows, a row tile of 64), each
    # against the same layer with the compiler's grouped product in its
    # place
    ex = LMSpec(family="window_moe", n_heads=8, n_kv=2, head_dim=128,
                n_experts=64, experts_per_tok=4, expert_width=512,
                score_fn="sigmoid", route_scale=2.448, experts_first=16,
                experts_held=8)
    assert experts.expert_row_tile(4096, 64) == 128
    assert experts.expert_row_tile(256, 64) == 64
    eblk = {"router": normal((1024, 64), jnp.bfloat16),
            "router_bias": jnp.zeros((64,), jnp.float32),
            "ewi": normal((8, 1024, 1024), jnp.bfloat16) * 0.03,
            "ewd": normal((8, 512, 1024), jnp.bfloat16) * 0.03}

    def layer(g, live):
        return experts.expert_layer(eblk, g, live, ex, jnp.bfloat16)

    def layer_by_ragged_dot(g, live):
        kernel = experts.grouped
        experts.grouped = lambda xs, w, counts, n: jax.lax.ragged_dot(
            xs, w, counts)
        try:
            return layer(g, live)
        finally:
            experts.grouped = kernel

    check("grouped_matmul", layer,
          (normal((1024, 1024), jnp.bfloat16), jnp.arange(1024) < 1000),
          layer_by_ragged_dot, 3e-2)
    check("grouped_matmul_decode", layer,
          (normal((64, 1024), jnp.bfloat16), jnp.arange(64) < 63),
          layer_by_ragged_dot, 3e-2)

    # paged kernels at the LLM legs' geometry, MHA and GQA
    hd, bs, nb = LLM["d_model"] // LLM["n_heads"], 16, 64
    nh = LLM["n_heads"]

    def gathered(pool, table, g):
        flat = pool[table].reshape(table.shape[:-1] + (-1,) + pool.shape[2:])
        return jnp.repeat(flat, g, axis=-2).astype(jnp.float32)

    for n_kv in (nh, 2):
        kp, vp = (normal((nb, bs, n_kv, hd)) for _ in range(2))
        tables = jnp.asarray(
            1 + rng.permutation(nb - 1)[:32].reshape(4, 8), jnp.int32)
        pos = jnp.asarray([3, 17, 64, 127], jnp.int32)

        def decode_ref(q, kp, vp, tables, pos, g=nh // n_kv):
            kc, vc = gathered(kp, tables, g), gathered(vp, tables, g)
            s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), kc,
                           precision="highest") * hd ** -0.5
            s = jnp.where(jnp.arange(kc.shape[1])[None, None, :]
                          <= pos[:, None, None], s, -1e30)
            return jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(s, -1), vc,
                              precision="highest")

        check(f"paged_decode_attn_kv{n_kv}", pp.paged_decode_attn,
              (normal((4, nh, hd), jnp.bfloat16), kp, vp, tables, pos),
              decode_ref, 3e-2)

        def prefill_ref(q, kp, vp, table, pos0, g=nh // n_kv):
            kc, vc = gathered(kp, table, g), gathered(vp, table, g)
            s = jnp.einsum("hqd,khd->hqk", q.astype(jnp.float32), kc,
                           precision="highest") * hd ** -0.5
            qpos = pos0 + jnp.arange(q.shape[1])
            s = jnp.where(jnp.arange(kc.shape[0])[None, None, :]
                          <= qpos[None, :, None], s, -1e30)
            return jnp.einsum("hqk,khd->hqd", jax.nn.softmax(s, -1), vc,
                              precision="highest")

        for s_c in (16, 128):
            check(f"paged_prefill_attn_kv{n_kv}_c{s_c}",
                  pp.paged_prefill_attn,
                  (normal((nh, s_c, hd), jnp.bfloat16), kp, vp, tables[0],
                   jnp.int32(32)), prefill_ref, 3e-2)
    return {"kernels": len(errs), "max_abs_err": round(max(errs.values()), 5)}


# -- label pipeline ----------------------------------------------------------

def _label_reference():
    """Reference logits per frame: a direct jit of the zoo model on the
    seeded frames `videotestsrc pattern=random` emits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.models.zoo import build_model

    rng = np.random.default_rng(FRAME_SEED)
    frames = [rng.integers(0, 256, size=(224, 224, 3), dtype=np.uint8)
              for _ in range(N_FRAMES)]
    bundle = build_model("mobilenet_v2")
    direct = jax.jit(lambda p, x: bundle.fn(
        p, (x.astype(jnp.float32) + -127.5) / 127.5))
    logits = [np.asarray(direct(bundle.params, f[None]))[0] for f in frames]
    for l in logits:
        assert l.shape == (1001,) and np.isfinite(l).all(), l.shape
    return logits


def _run_label(decoder: str, filter_props: str = ""):
    """Run the README label pipeline to EOS; returns (sink buffers, the
    filter's stats row, runner)."""
    import nnstreamer_tpu as nns

    pipe = nns.parse_launch(
        f"videotestsrc pattern=random seed={FRAME_SEED} "
        f"num-buffers={N_FRAMES} ! tensor_converter ! "
        f"tensor_transform mode=arithmetic option={NORMALIZE} ! "
        f"tensor_filter name=f model=zoo://mobilenet_v2 {filter_props} ! "
        f"tensor_decoder mode=image_labeling {decoder} ! "
        f"tensor_sink name=out")
    runner = nns.PipelineRunner(pipe)
    try:
        runner.start()
        runner.wait(600)
    finally:
        runner.stop()
    assert runner._error is None, runner._error
    return pipe.get("out").results, runner.stats()["f"], runner


def _check_classes(bufs, logits, index_of, score_of=None) -> int:
    """Every frame accounted for, in order, and the class the pipeline
    chose is the reference's best within bf16 rounding (the zoo model
    computes in bf16: its top logits tie or sit one step apart, and a
    differently fused program may break such a tie the other way).
    Returns how many classes were exactly the reference argmax."""
    assert len(bufs) == N_FRAMES, f"{len(bufs)} of {N_FRAMES} frames out"
    pts = [b.pts for b in bufs]
    assert pts == sorted(pts) and len(set(pts)) == N_FRAMES, pts
    exact = 0
    for i, (b, ref) in enumerate(zip(bufs, logits)):
        idx = index_of(b)
        tol = 4 * _bf16_ulp(float(abs(ref).max()))
        assert 0 <= idx < ref.size, (i, idx)
        assert ref[idx] >= ref.max() - tol, \
            f"frame {i}: class {idx} scores {ref[idx]}, best {ref.max()}"
        if score_of is not None:
            assert abs(score_of(b) - ref[idx]) <= tol, \
                f"frame {i}: score {score_of(b)} vs reference {ref[idx]}"
        exact += int(idx == int(ref.argmax()))
    return exact


def _check_window(stats: dict) -> dict:
    """The compiled steady-state window armed, ran, and never errored."""
    assert stats["buffers"] == N_FRAMES, stats["buffers"]
    assert stats["loop_entries"] > 0, stats
    assert stats["loop_bails"].get("error", 0) == 0, stats["loop_bails"]
    return {"loop_entries": stats["loop_entries"],
            "compiled_steps": stats["compiled_steps"],
            "loop_bails": stats["loop_bails"]}


def leg_label_host(ref) -> dict:
    bufs, stats, _ = _run_label("")
    exact = _check_classes(
        bufs, ref, lambda b: int(b.meta["label_index"]),
        lambda b: float(b.meta["score"]))
    return {"frames": len(bufs), "argmax_exact": exact,
            **_check_window(stats)}


def leg_label_device(ref) -> dict:
    import numpy as np

    bufs, stats, _ = _run_label("device=true")
    for b in bufs:
        t = np.asarray(b.tensors[0])
        assert t.shape == (1,) and t.dtype == np.int32, (t.shape, t.dtype)
    exact = _check_classes(
        bufs, ref, lambda b: int(np.asarray(b.tensors[0])[0]))
    return {"frames": len(bufs), "argmax_exact": exact,
            **_check_window(stats)}


# -- tensor_llm --------------------------------------------------------------

def _register_llm() -> str:
    """Seeded bf16 transformer at the smoke width, in the model store."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.models import transformer as T
    from nnstreamer_tpu.serving.store import get_store

    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), T.init_params(seed=0, **LLM))
    get_store().register("chip_smoke_llm",
                         ModelBundle(fn=None, params=params))
    return "store://chip_smoke_llm"


def _prompts(n: int, lo: int, hi: int):
    import numpy as np

    rng = np.random.default_rng(1)
    return [rng.integers(0, LLM["vocab"], size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def _run_llm(model: str, prompts, trace: bool = False, **llm_props):
    """appsrc ! tensor_llm ! tensor_sink (the shape of `python -m
    nnstreamer_tpu llm`): every request pushed, run to EOS. Returns
    ({request: tokens}, the element's stats; with `trace` also its
    kernel-tagged backend span counts under "kernel_spans")."""
    import numpy as np

    import nnstreamer_tpu as nns
    from nnstreamer_tpu.elements import AppSrc, TensorLLM, TensorSink
    from nnstreamer_tpu.tensor.buffer import TensorBuffer
    from nnstreamer_tpu.tensor.info import TensorFormat, TensorsSpec

    src = AppSrc(name="src", spec=TensorsSpec(
        tensors=(), format=TensorFormat.FLEXIBLE))
    props = dict(n_heads=LLM["n_heads"], dtype="bfloat16", max_batch=8,
                 num_blocks=64, block_size=16, max_len=128,
                 max_new_tokens=LLM_NEW_TOKENS,
                 # all requests land in the first serving step, so both
                 # arms decode the same batch composition every step
                 admit_window_ms=50.0)
    props.update(llm_props)
    llm = TensorLLM(name="llm", model=model, **props)
    sink = TensorSink(name="sink")
    pipe = nns.Pipeline()
    for e in (src, llm, sink):
        pipe.add(e)
    pipe.link(src, llm)
    pipe.link(llm, sink)
    for i, p in enumerate(prompts):
        src.push(TensorBuffer(tensors=(p,), pts=i,
                              meta={"llm": {"request_id": f"req{i}"}}))
    src.end()
    runner = nns.PipelineRunner(pipe, trace=trace)
    try:
        runner.start()
        runner.wait(600)
    finally:
        runner.stop()
    assert runner._error is None, runner._error
    tokens, done = {}, set()
    for b in sink.results:
        m = b.meta["llm"]
        tokens.setdefault(m["request_id"], []).extend(
            int(t) for t in np.asarray(b.tensors[0]))
        if m["done"]:
            done.add(m["request_id"])
    want = {f"req{i}" for i in range(len(prompts))}
    assert done == want, f"requests finished: {sorted(done)}"
    for rid, toks in tokens.items():
        assert len(toks) == props["max_new_tokens"], (rid, toks)
        assert all(0 <= t < LLM["vocab"] for t in toks), (rid, toks)
    stats = llm.extra_stats()
    assert stats["requests_in"] == stats["finished"] == len(prompts), stats
    if trace:
        stats["kernel_spans"] = {
            kernel: n for (_, kernel), n in
            runner.tracer.kernel_spans().items()}
    return tokens, stats


def leg_llm(model: str, kernel: str, reference=None):
    toks, stats = _run_llm(model, _prompts(LLM_REQUESTS, 9, 16),
                           paged_kernel=kernel)
    ex = stats["executor"]
    other = "xla" if kernel == "pallas" else "pallas"
    assert ex["paged_kernel"] == kernel, ex
    assert ex["kernel_invokes"][kernel] > 0, ex
    # xla's whole-prompt prefill and every decode step count under xla;
    # pallas routes prefill through the chunk family, so nothing in that
    # arm may have been served by XLA
    assert ex["kernel_invokes"][other] == 0, ex["kernel_invokes"]
    assert ex["decode_steps"] >= LLM_NEW_TOKENS - 1, ex
    if reference is not None:
        assert toks == reference, \
            f"greedy tokens differ: {kernel} {toks} vs {reference}"
    return toks, {"requests": len(toks), "tokens": stats["tokens_out"],
                  "kernel_invokes": ex["kernel_invokes"],
                  "compiles": ex["compile_count"]}


# -- the sparse-expert family ---------------------------------------------------

SPARSE = dict(d=64, heads=8, kv=2, hd=16, experts=8, per_tok=2, width=32,
              idx_heads=2, idx_dim=8, topk=8, layers=2, vocab=256)


def _sparse_bundle(device):
    """Seeded float32 weights of the tiny sparse-expert model on
    `device`, in the family's hand-over layout, with its description."""
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.llm.spec import SPARSE_MOE, LMSpec

    c = SPARSE
    rng = np.random.default_rng(11)

    def w(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    ones = lambda n: np.ones((n,), np.float32)          # noqa: E731
    qw, kw = c["heads"] * c["hd"], c["kv"] * c["hd"]
    blocks = [{
        "ln1": ones(c["d"]), "wqkv": w(c["d"], qw + 2 * kw),
        "q_norm": ones(c["hd"]), "k_norm": ones(c["hd"]),
        "wo": w(qw, c["d"]),
        "widx": w(c["d"], c["idx_heads"] * c["idx_dim"] + c["idx_dim"]
                  + c["idx_heads"]),
        "ln2": ones(c["d"]), "router": w(c["d"], c["experts"]),
        "ewi": w(c["experts"], c["d"], 2 * c["width"]),
        "ewd": w(c["experts"], c["width"], c["d"]),
    } for _ in range(c["layers"])]
    params = {"embed": w(c["vocab"], c["d"]), "blocks": blocks,
              "ln_f": ones(c["d"]), "head": w(c["d"], c["vocab"])}
    spec = LMSpec(family=SPARSE_MOE, n_heads=c["heads"], n_kv=c["kv"],
                  head_dim=c["hd"], rope_theta=1e7, qk_norm=True,
                  idx_heads=c["idx_heads"], idx_dim=c["idx_dim"],
                  topk=c["topk"], n_experts=c["experts"],
                  experts_per_tok=c["per_tok"], expert_width=c["width"])
    return ModelBundle(fn=None, lm=spec, params=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), params))


def leg_llm_sparse_moe() -> dict:
    """The family through the element on the default device (the chip)
    against the same engine on this host's CPU device. Float32 products
    at `highest` on both, so that greedy tokens of random weights do not
    hang on the matrix unit's bfloat16 rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.llm.engine import LLMEngine
    from nnstreamer_tpu.serving.store import get_store

    rng = np.random.default_rng(5)
    # prompts on both sides of topk (8) and of the chunk (8)
    prompts = [rng.integers(0, SPARSE["vocab"], size=n).astype(np.int32)
               for n in (5, 12, 21, 33)]
    serving = dict(block_size=8, num_blocks=64, max_len=64, prefill_chunk=8)
    cpu = jax.devices("cpu")[0]
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        with jax.default_device(cpu):
            eng = LLMEngine(_sparse_bundle(cpu), dtype=jnp.float32,
                            max_batch=8, **serving)
            reqs = [eng.submit(p, req_id=f"req{i}",
                               max_new_tokens=LLM_NEW_TOKENS)
                    for i, p in enumerate(prompts)]
            eng.drain()
            want = {r.req_id: list(r.tokens) for r in reqs}
            eng.executor.close()
        get_store().register("chip_smoke_sparse_moe",
                             _sparse_bundle(jax.devices()[0]))
        toks, stats = _run_llm("store://chip_smoke_sparse_moe", prompts,
                               dtype="float32", paged_kernel="xla",
                               **serving)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    ex = stats["executor"]
    assert ex["family"] == "sparse_moe" and ex["chunk_prefills"] >= 10, ex
    assert ex["kv_tokens_selected"] > 0 and ex["expert_tokens"] > 0, ex
    assert stats["cache"]["pools"] == 3, stats["cache"]
    assert toks == want, f"greedy tokens differ: chip {toks} vs cpu {want}"
    return {"requests": len(toks), "tokens": stats["tokens_out"],
            "chunk_prefills": ex["chunk_prefills"],
            "experts_touched_sum": ex["experts_touched_sum"]}


# -- the hybrid family ----------------------------------------------------------

HYBRID = dict(d=64, heads=4, kv=2, hd=16, width=128, vocab=256,
              kinds=("sparse", "linear", "linear", "sparse"))


def _hybrid_bundle(device):
    """Seeded float32 weights of the tiny hybrid model on `device`, in
    the family's hand-over layout, with its description."""
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.llm.spec import HYBRID as FAMILY
    from nnstreamer_tpu.llm.spec import LMSpec

    c = HYBRID
    rng = np.random.default_rng(13)

    def w(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    ones = lambda n: np.ones((n,), np.float32)          # noqa: E731
    aw, kw = c["heads"] * c["hd"], c["kv"] * c["hd"]

    def layer(kind):
        out = {"ln1": ones(c["d"]), "q_norm": ones(c["hd"]),
               "k_norm": ones(c["hd"]), "wg": w(c["d"], aw),
               "wo": w(aw, c["d"]), "ln2": ones(c["d"]),
               "wi": w(c["d"], 2 * c["width"]), "wd": w(c["width"], c["d"]),
               "wqkv": w(c["d"], 3 * aw if kind == "linear"
                         else aw + 2 * kw)}
        if kind == "linear":
            out["o_norm"] = ones(aw)
        return out

    params = {"embed": w(c["vocab"], c["d"]),
              "blocks": [layer(k) for k in c["kinds"]],
              "ln_f": ones(c["d"]), "head": w(c["d"], c["vocab"])}
    spec = LMSpec(family=FAMILY, n_heads=c["heads"], n_kv=c["kv"],
                  head_dim=c["hd"], qk_norm=True, layer_kinds=c["kinds"],
                  lin_heads=c["heads"], ck_kernel=8, ck_stride=4,
                  sel_block=8, sel_topk=3, sel_window=8, sel_init=1,
                  emb_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
                  logit_div=4.0)
    return ModelBundle(fn=None, lm=spec, params=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), params))


def leg_llm_hybrid() -> dict:
    """As `leg_llm_sparse_moe`, for the family that keeps a state slot a
    sequence beside its blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.llm.engine import LLMEngine
    from nnstreamer_tpu.serving.store import get_store

    rng = np.random.default_rng(6)
    # prompts on both sides of three selection blocks (24) and the chunk
    prompts = [rng.integers(0, HYBRID["vocab"], size=n).astype(np.int32)
               for n in (5, 12, 29, 41)]
    serving = dict(block_size=4, num_blocks=96, max_len=64, prefill_chunk=8)
    cpu = jax.devices("cpu")[0]
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        with jax.default_device(cpu):
            eng = LLMEngine(_hybrid_bundle(cpu), dtype=jnp.float32,
                            max_batch=8, **serving)
            reqs = [eng.submit(p, req_id=f"req{i}",
                               max_new_tokens=LLM_NEW_TOKENS)
                    for i, p in enumerate(prompts)]
            eng.drain()
            want = {r.req_id: list(r.tokens) for r in reqs}
            eng.executor.close()
        get_store().register("chip_smoke_hybrid",
                             _hybrid_bundle(jax.devices()[0]))
        toks, stats = _run_llm("store://chip_smoke_hybrid", prompts,
                               dtype="float32", paged_kernel="xla",
                               **serving)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    ex, cache = stats["executor"], stats["cache"]
    assert ex["family"] == "hybrid" and ex["chunk_prefills"] >= 10, ex
    assert ex["kv_tokens_selected"] > 0 and ex["state_bytes_rw"] > 0, ex
    assert cache["pools"] == 4 and cache["state_slots_used"] == 0, cache
    assert toks == want, f"greedy tokens differ: chip {toks} vs cpu {want}"
    return {"requests": len(toks), "tokens": stats["tokens_out"],
            "chunk_prefills": ex["chunk_prefills"],
            "state_bytes_rw": ex["state_bytes_rw"]}


# -- the window family ------------------------------------------------------------

WINDOWED = dict(d=64, heads=8, kv=2, hd=16, dense_width=160, width=32,
                experts=8, first=2, held=4, per_tok=2, vocab=256, window=8,
                kinds=("window", "full", "window"))


def _window_bundle(device):
    """Seeded float32 weights of the tiny window-family model on
    `device`, in the family's hand-over layout, with its description."""
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.llm.spec import WINDOW_MOE, LMSpec

    c = WINDOWED
    rng = np.random.default_rng(17)

    def w(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    ones = lambda n: np.ones((n,), np.float32)          # noqa: E731
    qw, kw = c["heads"] * c["hd"], c["kv"] * c["hd"]

    def layer(dense):
        out = {f"ln{i}": ones(c["d"]) for i in (1, 2, 3, 4)}
        out.update(wqkv=w(c["d"], qw + 2 * kw), q_norm=ones(c["hd"]),
                   k_norm=ones(c["hd"]), wg=w(c["d"], qw), wo=w(qw, c["d"]))
        if dense:
            out.update(wi=w(c["d"], 2 * c["dense_width"]),
                       wd=w(c["dense_width"], c["d"]))
            return out
        out.update(router=w(c["d"], c["experts"]),
                   router_bias=rng.uniform(-0.02, 0.02, c["experts"])
                   .astype(np.float32),
                   ewi=w(c["held"], c["d"], 2 * c["width"]),
                   ewd=w(c["held"], c["width"], c["d"]),
                   swi=w(c["d"], 2 * c["width"]), swd=w(c["width"], c["d"]))
        return out

    params = {"embed": w(c["vocab"], c["d"]),
              "blocks": [layer(i == 0) for i in range(len(c["kinds"]))],
              "ln_f": ones(c["d"]), "head": w(c["d"], c["vocab"])}
    spec = LMSpec(family=WINDOW_MOE, n_heads=c["heads"], n_kv=c["kv"],
                  head_dim=c["hd"], layer_kinds=c["kinds"],
                  window=c["window"], dense_layers=1,
                  dense_width=c["dense_width"],
                  shared_width=c["width"], n_experts=c["experts"],
                  experts_per_tok=c["per_tok"], expert_width=c["width"],
                  score_fn="sigmoid", route_scale=2.448,
                  experts_first=c["first"], experts_held=c["held"],
                  emb_scale=8.0, norm_eps=1e-5)
    return ModelBundle(fn=None, lm=spec, params=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), params))


def leg_llm_window_moe() -> dict:
    """As `leg_llm_sparse_moe`, for the family that keeps two tables a
    sequence: the full layers' and the window layers', whose blocks
    behind the window are given back and granted again."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.llm.engine import LLMEngine
    from nnstreamer_tpu.serving.store import get_store

    rng = np.random.default_rng(7)
    # prompts inside the window (8), across it and across the chunk (8)
    prompts = [rng.integers(0, WINDOWED["vocab"], size=n).astype(np.int32)
               for n in (5, 12, 29, 41)]
    serving = dict(block_size=4, num_blocks=96, max_len=64, prefill_chunk=8)
    cpu = jax.devices("cpu")[0]
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        with jax.default_device(cpu):
            eng = LLMEngine(_window_bundle(cpu), dtype=jnp.float32,
                            max_batch=8, **serving)
            reqs = [eng.submit(p, req_id=f"req{i}",
                               max_new_tokens=LLM_NEW_TOKENS)
                    for i, p in enumerate(prompts)]
            eng.drain()
            want = {r.req_id: list(r.tokens) for r in reqs}
            eng.executor.close()
        get_store().register("chip_smoke_window_moe",
                             _window_bundle(jax.devices()[0]))
        toks, stats = _run_llm("store://chip_smoke_window_moe", prompts,
                               dtype="float32", paged_kernel="xla",
                               **serving)
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    ex, cache = stats["executor"], stats["cache"]
    assert ex["family"] == "window_moe" and ex["chunk_prefills"] >= 10, ex
    assert ex["expert_pairs_held"] > 0 and ex["expert_pairs_away"] > 0, ex
    assert cache["pools"] == 4 and cache["window_blocks_freed"] > 0, cache
    assert cache["window"]["blocks_used"] == 0, cache
    assert toks == want, f"greedy tokens differ: chip {toks} vs cpu {want}"
    return {"requests": len(toks), "tokens": stats["tokens_out"],
            "chunk_prefills": ex["chunk_prefills"],
            "window_blocks_freed": cache["window_blocks_freed"]}


LATENT = dict(d=64, heads=4, q_rank=24, kv_rank=16, nope=8, rope=4, v=8,
              dense_width=160, width=32, experts=16, first=4, held=2,
              groups=8, topk_group=3, per_tok=4, vocab=256, layers=3)


def _latent_bundle(device):
    """Seeded float32 weights of the tiny latent-family model on `device`,
    in the family's hand-over layout, with its description."""
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.llm.spec import LATENT_MOE, LMSpec

    c = LATENT
    rng = np.random.default_rng(23)

    def w(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    ones = lambda n: np.ones((n,), np.float32)          # noqa: E731
    h = c["heads"]

    def layer(dense):
        out = dict(ln1=ones(c["d"]), ln2=ones(c["d"]),
                   wqa=w(c["d"], c["q_rank"]), q_norm=ones(c["q_rank"]),
                   wqb=w(c["q_rank"], h * (c["nope"] + c["rope"])),
                   wkva=w(c["d"], c["kv_rank"] + c["rope"]),
                   kv_norm=ones(c["kv_rank"]),
                   wkvb=w(c["kv_rank"], h * (c["nope"] + c["v"])),
                   wo=w(h * c["v"], c["d"]))
        if dense:
            out.update(wi=w(c["d"], 2 * c["dense_width"]),
                       wd=w(c["dense_width"], c["d"]))
            return out
        out.update(router=w(c["d"], c["experts"]),
                   ewi=w(c["held"], c["d"], 2 * c["width"]),
                   ewd=w(c["held"], c["width"], c["d"]),
                   swi=w(c["d"], 4 * c["width"]),
                   swd=w(2 * c["width"], c["d"]))
        return out

    params = {"embed": w(c["vocab"], c["d"]),
              "blocks": [layer(i == 0) for i in range(c["layers"])],
              "ln_f": ones(c["d"]), "head": w(c["d"], c["vocab"])}
    spec = LMSpec(family=LATENT_MOE, n_heads=h, q_rank=c["q_rank"],
                  kv_rank=c["kv_rank"], nope_dim=c["nope"],
                  rope_dim=c["rope"], v_dim=c["v"], yarn_factor=40.0,
                  yarn_orig_len=16, yarn_mscale=0.707,
                  yarn_mscale_all_dim=0.707, dense_layers=1,
                  dense_width=c["dense_width"], shared_width=2 * c["width"],
                  n_experts=c["experts"], experts_per_tok=c["per_tok"],
                  expert_width=c["width"], n_group=c["groups"],
                  topk_group=c["topk_group"], route_norm=False,
                  route_scale=16.0, experts_first=c["first"],
                  experts_held=c["held"])
    return ModelBundle(fn=None, lm=spec, params=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), params))


def leg_llm_latent_moe() -> dict:
    """As `leg_llm_sparse_moe`, for the family whose pool holds a latent
    and a roped key a token and no values: once with chunks of 8, which
    attend absorbed, once with chunks of 32, which attend expanded
    (`latent_moe.expanded_attend`: these widths cross at 16 queries)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.llm.engine import LLMEngine
    from nnstreamer_tpu.serving.store import get_store

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, LATENT["vocab"], size=n).astype(np.int32)
               for n in (5, 12, 29, 41)]
    cpu = jax.devices("cpu")[0]
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    out = {}
    try:
        get_store().register("chip_smoke_latent_moe",
                             _latent_bundle(jax.devices()[0]))
        for form, chunk in (("absorbed", 8), ("expanded", 32)):
            serving = dict(block_size=4, num_blocks=96, max_len=64,
                           prefill_chunk=chunk)
            with jax.default_device(cpu):
                eng = LLMEngine(_latent_bundle(cpu), dtype=jnp.float32,
                                max_batch=8, **serving)
                reqs = [eng.submit(p, req_id=f"req{i}",
                                   max_new_tokens=LLM_NEW_TOKENS)
                        for i, p in enumerate(prompts)]
                eng.drain()
                want = {r.req_id: list(r.tokens) for r in reqs}
                eng.executor.close()
            toks, stats = _run_llm("store://chip_smoke_latent_moe", prompts,
                                   dtype="float32", paged_kernel="xla",
                                   **serving)
            ex, cache = stats["executor"], stats["cache"]
            assert ex["family"] == "latent_moe", ex
            assert ex["expert_pairs_held"] > 0 < ex["expert_pairs_away"], ex
            assert (ex["latents_expanded"] > 0) == (form == "expanded"), ex
            assert cache["pools"] == 2 and cache["blocks_used"] == 0, cache
            assert toks == want, \
                f"{form}: greedy tokens differ: chip {toks} vs cpu {want}"
            out[f"chunk_prefills_{form}"] = ex["chunk_prefills"]
            out["tokens"] = out.get("tokens", 0) + stats["tokens_out"]
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    return out


DELTA = dict(d=64, kinds="KKLKL", kda_heads=2, kda_dim=8, conv=4, heads=4,
             kv_rank=16, nope=8, rope=4, v=8, dense_width=160, width=32,
             experts=16, first=4, held=4, per_tok=4, vocab=256)


def _delta_bundle(device, kda_dim: int = DELTA["kda_dim"]):
    """Seeded float32 weights of the tiny delta-family model on `device`,
    in the family's hand-over layout, with its description; KDA heads of
    `kda_dim`."""
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.xla import ModelBundle
    from nnstreamer_tpu.llm.spec import DELTA_MOE, KDA, LATENT, LMSpec

    c = dict(DELTA, kda_dim=kda_dim)
    rng = np.random.default_rng(29)

    def w(*shape):
        lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
        return rng.uniform(-lim, lim, shape).astype(np.float32)

    ones = lambda n: np.ones((n,), np.float32)          # noqa: E731
    h, hd, r = c["heads"], c["kda_heads"] * c["kda_dim"], c["kda_dim"]

    def layer(kind, dense):
        out = dict(ln1=ones(c["d"]), ln2=ones(c["d"]))
        if kind == "K":
            out.update(
                wqkv=w(c["d"], 3 * hd),
                conv=rng.uniform(-0.5, 0.5, (c["conv"], 3 * hd))
                .astype(np.float32),
                wfa=w(c["d"], r), wfb=w(r, hd),
                dt_bias=rng.uniform(-4.0, -1.0, (hd,)).astype(np.float32),
                a_log=np.log(rng.uniform(1.0, 16.0, (c["kda_heads"],)))
                .astype(np.float32),
                wb=w(c["d"], c["kda_heads"]), wga=w(c["d"], r),
                wgb=w(r, hd), o_norm=ones(c["kda_dim"]), wo=w(hd, c["d"]))
        else:
            out.update(wq=w(c["d"], h * (c["nope"] + c["rope"])),
                       wkva=w(c["d"], c["kv_rank"] + c["rope"]),
                       kv_norm=ones(c["kv_rank"]),
                       wkvb=w(c["kv_rank"], h * (c["nope"] + c["v"])),
                       wo=w(h * c["v"], c["d"]))
        if dense:
            out.update(wi=w(c["d"], 2 * c["dense_width"]),
                       wd=w(c["dense_width"], c["d"]))
            return out
        out.update(router=w(c["d"], c["experts"]),
                   router_bias=rng.uniform(-0.02, 0.02, (c["experts"],))
                   .astype(np.float32),
                   ewi=w(c["held"], c["d"], 2 * c["width"]),
                   ewd=w(c["held"], c["width"], c["d"]),
                   swi=w(c["d"], 2 * c["width"]), swd=w(c["width"], c["d"]))
        return out

    params = {"embed": w(c["vocab"], c["d"]),
              "blocks": [layer(k, i == 0) for i, k in enumerate(c["kinds"])],
              "ln_f": ones(c["d"]), "head": w(c["d"], c["vocab"])}
    spec = LMSpec(family=DELTA_MOE, n_heads=h, head_dim=c["kda_dim"],
                  lin_heads=c["kda_heads"], conv_kernel=c["conv"],
                  q_rank=0, roped=False,
                  layer_kinds=tuple(KDA if k == "K" else LATENT
                                    for k in c["kinds"]),
                  kv_rank=c["kv_rank"], nope_dim=c["nope"],
                  rope_dim=c["rope"], v_dim=c["v"], dense_layers=1,
                  dense_width=c["dense_width"], shared_width=c["width"],
                  n_experts=c["experts"], experts_per_tok=c["per_tok"],
                  expert_width=c["width"], score_fn="sigmoid",
                  route_scale=2.446, experts_first=c["first"],
                  experts_held=c["held"], norm_eps=1e-5)
    return ModelBundle(fn=None, lm=spec, params=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), params))


def leg_llm_delta_moe() -> dict:
    """As `leg_llm_latent_moe`, for the family that keeps two kinds of
    cache: once with chunks of 8 (one run of the closed form a chunk, the
    latent layers absorbed), once with chunks of 32 (runs of 16, the
    latent layers expanded); decode through the states and tails by slot,
    the states gathered (heads of 8). Then once more with KDA heads of 128,
    the width at which the decode step moves a row's state through its
    slot in one kernel (`pallas_state.delta_decode_update`, by
    `delta_moe.fused_state`'s rule): lowered on the chip, interpreted in
    the engine on the host's CPU device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.llm import delta_moe
    from nnstreamer_tpu.llm.engine import LLMEngine
    from nnstreamer_tpu.serving.store import get_store

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, DELTA["vocab"], size=n).astype(np.int32)
               for n in (5, 12, 29, 41)]
    cpu = jax.devices("cpu")[0]
    was, run = jax.config.jax_default_matmul_precision, delta_moe.RUN
    jax.config.update("jax_default_matmul_precision", "highest")
    delta_moe.RUN = 16
    out = {}
    try:
        get_store().register("chip_smoke_delta_moe",
                             _delta_bundle(jax.devices()[0]))
        get_store().register("chip_smoke_delta_moe_wide",
                             _delta_bundle(jax.devices()[0], 128))
        for form, chunk, kda_dim in (("absorbed", 8, DELTA["kda_dim"]),
                                     ("expanded", 32, DELTA["kda_dim"]),
                                     ("fused_state", 8, 128)):
            serving = dict(block_size=4, num_blocks=96, max_len=64,
                           prefill_chunk=chunk)
            fused = form == "fused_state"
            with jax.default_device(cpu):
                eng = LLMEngine(_delta_bundle(cpu, kda_dim),
                                dtype=jnp.float32, max_batch=8, **serving)
                reqs = [eng.submit(p, req_id=f"req{i}",
                                   max_new_tokens=LLM_NEW_TOKENS)
                        for i, p in enumerate(prompts)]
                eng.drain()
                want = {r.req_id: list(r.tokens) for r in reqs}
                eng.executor.close()
            toks, stats = _run_llm(
                "store://chip_smoke_delta_moe" + "_wide" * fused, prompts,
                dtype="float32", paged_kernel="xla", **serving)
            ex, cache = stats["executor"], stats["cache"]
            assert ex["family"] == "delta_moe", ex
            assert ex["expert_pairs_held"] > 0 < ex["expert_pairs_away"], ex
            assert (ex["latents_expanded"] > 0) == (form == "expanded"), ex
            assert ex["state_bytes_rw"] > 0 < ex["tail_bytes_rw"], ex
            took, other = (("fused", "gathered") if fused
                           else ("gathered", "fused"))
            assert ex[f"state_steps_{took}"] == ex["decode_steps"], ex
            assert ex[f"state_steps_{other}"] == 0, ex
            assert cache["pools"] == 4 and cache["blocks_used"] == 0, cache
            assert cache["state_slots_used"] == 0, cache
            assert toks == want, \
                f"{form}: greedy tokens differ: chip {toks} vs cpu {want}"
            out[f"chunk_prefills_{form}"] = ex["chunk_prefills"]
            out[f"delta_runs_{form}"] = ex["delta_runs"]
            out["tokens"] = out.get("tokens", 0) + stats["tokens_out"]
    finally:
        jax.config.update("jax_default_matmul_precision", was)
        delta_moe.RUN = run
    return out


# -- four chips --------------------------------------------------------------

def leg_multichip(model: str, ref) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    devs = jax.devices()[:4]
    ids = [d.id for d in devs]
    assert len(set(ids)) == 4, ids
    before = [d.memory_stats()["bytes_in_use"] for d in devs]

    # devices=4: one replica per chip, every chip serves frames
    bufs, stats, _ = _run_label("device=true", "devices=4")
    assert "replica_decline" not in stats, stats["replica_decline"]
    assert stats["replica_devices"] == 4 and stats["replica_live"] == 4
    rows = stats["replicas"]
    served = {devs[r["device"]].id: r["invokes"] for r in rows}
    assert sorted(served) == sorted(ids), served
    assert all(n > 0 for n in served.values()), served
    assert sum(served.values()) == N_FRAMES and stats["replica_errors"] == 0
    exact = _check_classes(
        bufs, ref, lambda b: int(np.asarray(b.tensors[0])[0]))
    # the weights went to four chips, not four times to chip 0: the
    # three chips that held nothing before this leg each grew by a model
    grew = [d.memory_stats()["peak_bytes_in_use"] - b
            for d, b in zip(devs[1:], before[1:])]
    assert all(g > 4 << 20 for g in grew), f"HBM growth on chips 1-3 {grew}"

    # shards=4 == shards=1, token for token (canonical blocking)
    prompts = _prompts(2, 9, 16)
    one, _ = _run_llm(model, prompts, shards=1)
    four, st4 = _run_llm(model, prompts, shards=4)
    assert st4["executor"]["shards"] == 4
    assert len(set(st4["executor"]["shard_chips"])) == 4
    assert four == one, f"shards=4 {four} vs shards=1 {one}"

    # ring prefill: a 512-token prompt over four chips is 128 rows a
    # chip, where ring_attention takes the Pallas block kernel by itself
    mesh = Mesh(np.array(devs), ("sp",))
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 512, 8, 128)), jnp.bfloat16)
               .astype(jnp.float32) for _ in range(3))
    ring = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh,
                                                  causal=True))
    assert "tpu_custom_call" in ring.lower(q, k, v).as_text()
    err = float(jnp.abs(ring(q, k, v)
                        - reference_attention(q, k, v, causal=True)).max())
    assert err <= 3e-2, f"ring attention over 4 chips: max abs err {err}"
    long_prompt = [np.random.default_rng(3).integers(
        0, LLM["vocab"], size=500).astype(np.int32)]
    _, st_ring = _run_llm(model, long_prompt, trace=True, shards=4,
                          ring_prefill_min=256, max_len=640, num_blocks=96)
    assert st_ring["kernel_spans"].get("ring", 0) > 0, st_ring["kernel_spans"]
    return {"replica_invokes": served, "argmax_exact": exact,
            "hbm_growth_mib": [g >> 20 for g in grew],
            "shards4_equals_shards1": True,
            "ring_attention_err": round(err, 5)}


# -- driver ------------------------------------------------------------------

def main() -> int:
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{dev['platform']!r} ({dev['kind']}, {dev['count']} "
              f"device(s)); there is no CPU mode", file=sys.stderr)
        return 2

    from importlib.metadata import version

    import jaxlib

    from nnstreamer_tpu.serving.compile_cache import enable_compile_cache

    print(f"platform {dev['platform']}  device_kind {dev['kind']}  "
          f"devices {dev['count']}")
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {version('libtpu')}")
    print(f"compile cache {enable_compile_cache()}", flush=True)

    failed = []

    def leg(name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"leg {name}: FAILED after "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
            return None
        info = out[1] if isinstance(out, tuple) else out
        print(f"leg {name}: ok ({time.perf_counter() - t0:.1f}s) "
              f"{json.dumps(info)}", flush=True)
        return out

    leg("kernels", leg_kernels)
    ref = leg("label_reference",
              lambda: (_label_reference(), {"frames": N_FRAMES}))
    ref = ref and ref[0]            # the logits; None if the leg failed
    if ref:
        leg("label_host", leg_label_host, ref)
        leg("label_device", leg_label_device, ref)
    model = _register_llm()
    xla = leg("llm_xla", leg_llm, model, "xla")
    leg("llm_pallas", leg_llm, model, "pallas", xla and xla[0])
    leg("llm_sparse_moe", leg_llm_sparse_moe)
    leg("llm_hybrid", leg_llm_hybrid)
    leg("llm_window_moe", leg_llm_window_moe)
    leg("llm_latent_moe", leg_llm_latent_moe)
    leg("llm_delta_moe", leg_llm_delta_moe)
    if dev["count"] >= 4 and ref:
        leg("multichip", leg_multichip, model, ref)
    else:
        print(f"multichip: not run ({dev['count']} device)")

    if failed:
        print(f"chip_smoke: FAILED legs: {', '.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
