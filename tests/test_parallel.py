"""parallel/ tests on the 8-device virtual CPU mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from nnstreamer_tpu.parallel import (
    MeshSpec,
    make_mesh,
    make_train_step,
    shard_params)
from nnstreamer_tpu.parallel.train import init_state, shard_state


def test_mesh_spec_resolution(eight_cpu_devices):
    # resolve order follows AXES = (dp, pp, tp, ep, sp)
    assert MeshSpec(dp=-1, tp=2, sp=1).resolve(8) == (4, 1, 2, 1, 1)
    assert MeshSpec(dp=2, tp=2, sp=2).resolve(8) == (2, 1, 2, 1, 2)
    assert MeshSpec(dp=1, pp=4, ep=2).resolve(8) == (1, 4, 1, 2, 1)
    mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
    assert mesh.shape == {"dp": 2, "pp": 1, "tp": 2, "ep": 1, "sp": 2}
    with pytest.raises(Exception):
        MeshSpec(dp=3, tp=2, sp=1).resolve(8)


def test_shard_params_mobilenet(eight_cpu_devices):
    from nnstreamer_tpu.models import mobilenet_v2 as m

    mesh = make_mesh(MeshSpec(dp=4, tp=2, sp=1))
    params = m.init_params(width=0.35)
    sharded = shard_params(params, mesh)
    # conv kernels with tp-divisible out channels actually shard over tp
    w = sharded["stem"]["conv"]["w"]
    assert w.sharding.spec == P(None, None, None, "tp")
    # numerics unchanged after sharding
    x = jnp.ones((1, 64, 64, 3))
    a = m.apply(params, x, width=0.35, dtype=jnp.float32)
    b = m.apply(sharded, x, width=0.35, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_sharded_train_step_runs_and_matches_single(eight_cpu_devices):
    """dp+tp train step: loss must equal the unsharded step's loss."""
    from nnstreamer_tpu.models import mobilenet_v2 as m

    params = m.init_params(width=0.35, num_classes=16)
    opt = optax.sgd(0.1)
    loss_fn = lambda p, x, y: m.loss_fn(p, x, y, width=0.35, dtype=jnp.float32)
    x = jnp.ones((8, 32, 32, 3))
    y = jnp.arange(8) % 16

    # single-device reference
    step0 = make_train_step(loss_fn, opt, donate=False)
    _, loss_ref = step0(init_state(params, opt), x, y)

    mesh = make_mesh(MeshSpec(dp=4, tp=2, sp=1))
    state = shard_state(init_state(params, opt), mesh)
    step = make_train_step(loss_fn, opt, mesh=mesh,
                           batch_spec=(P("dp"), P("dp")), donate=False)
    state2, loss = step(state, x, y)
    assert int(state2.step) == 1
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-4)


def test_ring_attention_matches_reference(eight_cpu_devices):
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    B, S, H, D = 2, 64, 4, 16
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, D), jnp.float32)

    ref = reference_attention(q, k, v)
    out = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_causal(eight_cpu_devices):
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=4))
    key = jax.random.PRNGKey(1)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in jax.random.split(key, 3))
    ref = reference_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_mesh_dispatcher_batches(eight_cpu_devices):
    from nnstreamer_tpu.parallel.dispatch import MeshDispatcher

    mesh = make_mesh(MeshSpec(dp=8, tp=1, sp=1))

    def fn(params, x):  # toy model: mean over features + bias
        return x @ params["w"]

    params = {"w": jnp.eye(4)}
    d = MeshDispatcher(fn, params, mesh, bucket=8, max_delay_ms=1.0)
    try:
        futs = [d.submit(np.full((4,), i, np.float32)) for i in range(11)]
        outs = [f.result(30) for f in futs]
        for i, o in enumerate(outs):
            np.testing.assert_allclose(o[0], np.full((4,), i, np.float32))
        assert d.frames == 11
        assert d.batches >= 2
    finally:
        d.shutdown()


def test_mesh_dispatcher_shutdown_idempotent(eight_cpu_devices):
    """Regression: shutdown() must be callable repeatedly (finally
    blocks + supervised teardown paths both call it) without error and
    without re-running the teardown."""
    from nnstreamer_tpu.parallel.dispatch import MeshDispatcher

    mesh = make_mesh(MeshSpec(dp=8, tp=1, sp=1))
    d = MeshDispatcher(lambda p, x: x @ p["w"], {"w": jnp.eye(4)},
                       mesh, bucket=8, max_delay_ms=1.0)
    fut = d.submit(np.ones((4,), np.float32))
    np.testing.assert_allclose(fut.result(30)[0], np.ones(4, np.float32))
    d.shutdown()
    d.shutdown()                             # second call: strict no-op
    d.shutdown()


# -- pipeline parallelism (pp) ------------------------------------------------

def test_pipeline_matches_serial(eight_cpu_devices):
    from nnstreamer_tpu.parallel.pipeline import (
        pipeline_apply, reference_pipeline, stack_stage_params)

    mesh = make_mesh(MeshSpec(dp=1, pp=4))
    key = jax.random.PRNGKey(0)
    d = 16
    per_stage = []
    for i in range(4):
        k1, k2, key = jax.random.split(key, 3)
        per_stage.append({
            "w": jax.random.normal(k1, (d, d)) * d ** -0.5,
            "b": jax.random.normal(k2, (d,)) * 0.1,
        })

    def stage(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    x = jax.random.normal(key, (6, 3, d))     # 6 microbatches of 3 tokens
    stacked = stack_stage_params(per_stage)
    got = jax.jit(
        lambda s, x: pipeline_apply(stage, s, x, mesh=mesh))(stacked, x)
    want = reference_pipeline(stage, per_stage, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_single_microbatch(eight_cpu_devices):
    from nnstreamer_tpu.parallel.pipeline import (
        pipeline_apply, reference_pipeline, stack_stage_params)

    mesh = make_mesh(MeshSpec(dp=1, pp=8))
    per_stage = [{"w": jnp.eye(4) * (i + 1)} for i in range(8)]
    stage = lambda p, a: a @ p["w"]
    x = jnp.ones((1, 2, 4))
    got = pipeline_apply(stage, stack_stage_params(per_stage), x, mesh=mesh)
    want = reference_pipeline(stage, per_stage, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# -- expert parallelism (ep) --------------------------------------------------

def test_moe_matches_serial_when_capacity_ample(eight_cpu_devices):
    from nnstreamer_tpu.parallel.moe import (
        init_moe_params, moe_apply, moe_param_specs, reference_moe)
    from jax.sharding import NamedSharding

    mesh = make_mesh(MeshSpec(dp=1, ep=8))
    key = jax.random.PRNGKey(1)
    d, h, E, T = 8, 16, 8, 64
    params = init_moe_params(key, d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, d))
    specs = moe_param_specs()
    placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    xs = jax.device_put(x, NamedSharding(mesh, P("ep")))
    # capacity ≥ local tokens → zero drops → serial equivalence
    got = jax.jit(lambda p, x: moe_apply(p, x, mesh=mesh,
                                         capacity_factor=float(E)))(placed, xs)
    want = reference_moe(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_are_bounded_not_wrong(eight_cpu_devices):
    """With a tight capacity, dropped tokens produce zero output (the
    residual path carries them); surviving tokens still match serial."""
    from nnstreamer_tpu.parallel.moe import (
        init_moe_params, moe_apply, moe_param_specs, reference_moe)
    from jax.sharding import NamedSharding

    mesh = make_mesh(MeshSpec(dp=1, ep=8))
    d, h, E, T = 8, 16, 8, 64
    params = init_moe_params(jax.random.PRNGKey(1), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, d))
    specs = moe_param_specs()
    placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    xs = jax.device_put(x, NamedSharding(mesh, P("ep")))
    got = np.asarray(moe_apply(placed, xs, mesh=mesh, capacity_factor=1.0))
    want = np.asarray(reference_moe(params, x))
    for t in range(T):
        if np.allclose(got[t], 0.0):
            continue                     # dropped: zero contribution
        np.testing.assert_allclose(got[t], want[t], rtol=1e-4, atol=1e-4)


def test_moe_rejects_undivisible_experts(eight_cpu_devices):
    from nnstreamer_tpu.parallel.moe import init_moe_params, moe_apply

    mesh = make_mesh(MeshSpec(dp=1, ep=8))
    params = init_moe_params(jax.random.PRNGKey(0), 4, 8, 6)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="experts"):
        moe_apply(params, jnp.ones((16, 4)), mesh=mesh)


# -- multi-host entry points (single-process degenerate case) -----------------

def test_multihost_single_process_fallback(eight_cpu_devices):
    from nnstreamer_tpu.parallel import multihost

    # no coordinator configured → clean single-process fallback
    assert multihost.initialize() is False
    mesh = multihost.global_mesh(MeshSpec(dp=4, tp=2))
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2


def test_multihost_batch_and_fetch(eight_cpu_devices):
    from nnstreamer_tpu.parallel import multihost

    mesh = multihost.global_mesh(MeshSpec(dp=8))
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    gx = multihost.host_local_batch(mesh, x)
    assert gx.shape == (8, 2)
    y = jax.jit(lambda a: a * 2)(gx)
    out = multihost.fetch_replicated(y)
    np.testing.assert_array_equal(np.asarray(out), x * 2)


def test_transformer_seq_ring_attention_matches_serial(eight_cpu_devices):
    """Full-sequence transformer forward with sp-sharded ring attention
    equals the single-device forward (long-context path end-to-end)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import transformer as T

    mesh = make_mesh(MeshSpec(dp=1, sp=8))
    d, H, L, V, S = 32, 4, 2, 64, 32    # S divides sp=8
    params = T.init_params(d_model=d, n_heads=H, n_layers=L, vocab=V)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, V, (1, S)), jnp.int32)
    want = np.asarray(T.apply_seq(params, ids, n_heads=H))
    got = np.asarray(T.apply_seq(params, ids, n_heads=H, mesh=mesh))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_attention_pallas_block_matches_xla(eight_cpu_devices):
    """The Pallas flash block kernel inside the ring (interpret mode on
    the CPU mesh) equals the jnp block path."""
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=4))
    key = jax.random.PRNGKey(3)
    B, S, H, D = 1, 64, 2, 16    # s_local=16: kernel blocks of 16
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    for causal in (False, True):
        ref = reference_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal,
                             block_impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_with_batch_axis_dp(eight_cpu_devices):
    """dp×sp composition: batch sharded over dp AND sequence ring-
    attended over sp in one mesh matches the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nnstreamer_tpu.parallel import MeshSpec, make_mesh
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
    B, S, H, D = 4, 16, 2, 8
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    qs, ks, vs = (jax.device_put(
        t, NamedSharding(mesh, P("dp", "sp", None, None)))
        for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=mesh, axis="sp", batch_axis="dp",
        causal=True))(qs, ks, vs)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_ring_attention_batch_axis_pallas_block(eight_cpu_devices):
    """Same dp×sp composition through the Pallas block path (interpret
    mode on CPU) — guards the pallas shard_map's batch_axis spec."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nnstreamer_tpu.parallel import MeshSpec, make_mesh
    from nnstreamer_tpu.parallel.ring_attention import (
        reference_attention, ring_attention)

    mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
    B, S, H, D = 2, 32, 1, 8
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    qs, ks, vs = (jax.device_put(
        t, NamedSharding(mesh, P("dp", "sp", None, None)))
        for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=mesh, axis="sp", batch_axis="dp",
        causal=True, block_impl="pallas"))(qs, ks, vs)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4)


# -- dormant-module smoke (sharded-serving PR satellites) ---------------------

def test_mesh_spec_resolve_edge_cases(eight_cpu_devices):
    from nnstreamer_tpu.core.errors import PipelineError

    # a single wildcard soaks up every remaining device
    assert MeshSpec(dp=-1).resolve(8) == (8, 1, 1, 1, 1)
    assert MeshSpec(dp=1, tp=-1, sp=2).resolve(8) == (1, 1, 4, 1, 2)
    # exact fit with no wildcard
    assert MeshSpec(dp=2, tp=2, sp=2).resolve(8) == (2, 1, 2, 1, 2)
    # two wildcards are ambiguous — refused, never guessed
    with pytest.raises(PipelineError, match="at most one"):
        MeshSpec(dp=-1, tp=-1).resolve(8)
    # fixed axes that do not divide the device count
    with pytest.raises(PipelineError, match="divide"):
        MeshSpec(dp=3, tp=2).resolve(8)
    # oversubscription: more chips demanded than visible
    with pytest.raises(PipelineError):
        MeshSpec(dp=16).resolve(8)
    with pytest.raises(PipelineError):
        MeshSpec(dp=4, tp=4).resolve(8)


def test_shard_map_is_jax_shard_map():
    """Every shard_map consumer in parallel/ uses `jax.shard_map` itself
    (the `check_vma` spelling) — no experimental import, no shim."""
    import jax

    from nnstreamer_tpu.parallel import moe, pipeline, ring_attention

    assert moe.shard_map is jax.shard_map
    assert pipeline.shard_map is jax.shard_map
    assert ring_attention.shard_map is jax.shard_map


def test_block_attn_streaming_accumulator_matches_reference(
        eight_cpu_devices):
    """`_block_attn` is the online-softmax accumulator both the ring and
    the sharded-serving prefill lean on: feeding the K/V blocks through
    it sequentially (no mesh at all) must reproduce dense attention."""
    from nnstreamer_tpu.parallel.ring_attention import (
        NEG_INF, _block_attn, reference_attention)

    key = jax.random.PRNGKey(5)
    B, S, H, D, nblk = 2, 32, 2, 8, 4
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    m = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S), jnp.float32)
    o = jnp.zeros((B, S, H, D), jnp.float32)
    step = S // nblk
    for i in range(nblk):
        kb = k[:, i * step:(i + 1) * step]
        vb = v[:, i * step:(i + 1) * step]
        m, l, o = _block_attn(q, kb, vb, m, l, o)
    got = o / l.transpose(0, 2, 1)[..., None]
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_block_accumulator_exactly(
        eight_cpu_devices):
    """Ring attention on the sp mesh vs the same `_block_attn` chain run
    serially in ring-visit order: identical block count and order means
    the mesh only changes *where* blocks live, not the numerics."""
    from nnstreamer_tpu.parallel.ring_attention import (
        NEG_INF, _block_attn, ring_attention)

    n = 4
    mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=n))
    key = jax.random.PRNGKey(6)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    out = ring_attention(q, k, v, mesh=mesh)
    step = S // n
    rows = []
    for d in range(n):           # device d's query block
        qd = q[:, d * step:(d + 1) * step]
        m = jnp.full((B, H, step), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, step), jnp.float32)
        o = jnp.zeros((B, step, H, D), jnp.float32)
        for hop in range(n):     # ppermute ring visit order
            src = (d - hop) % n
            kb = k[:, src * step:(src + 1) * step]
            vb = v[:, src * step:(src + 1) * step]
            m, l, o = _block_attn(qd, kb, vb, m, l, o)
        rows.append(o / l.transpose(0, 2, 1)[..., None])
    want = jnp.concatenate(rows, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dryrun_composed_dp_tp_sp_numeric(eight_cpu_devices):
    """The driver gate's composed-mesh section (dp×tp×sp in one program
    + in-gate numeric check) on the virtual 8-device mesh."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import __graft_entry__ as g
    import jax

    err, shape = g._composed_dp_tp_sp(jax.devices(), 8)
    assert err < 5e-4
    assert shape == dict(dp=2, tp=2, sp=2)
