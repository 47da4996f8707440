"""Sharded serving: one mesh-sharded model across N chips (`shards=N`).

dp replicas (serving/placement.py) scale *traffic* — N chips, N whole
copies of the model. This subsystem scales the *model*: `shards=N`
opens ONE backend whose weights (and, for the LLM path, paged KV pool)
are partitioned across an N-chip `tp` mesh via `shard_map`, so a model
whose weights + KV exceed one chip's HBM serves from the group's
combined memory. Three layers:

**Canonical blocking — the bit-parity mechanism.** Every sharded
weight is split into a FIXED number of blocks (``FIXED_BLOCKS = 8``,
the largest supported group) along its megatron axis — wq/wk/wv and
the SwiGLU gate/up column-wise per head/feature block, wo/wd row-wise
per block, the LM head column-wise per vocab block. A group of N chips
holds 8/N contiguous blocks each; the compute graph is a loop over
*blocks*, never over *shards*: per-block matmuls have N-independent
shapes, row-parallel partial sums are `all_gather`\\ ed into the fixed
(8, …) block order and reduced by a fixed-order chain of adds instead
of a `psum` (whose reduction order would depend on N). Numerics are
therefore a function of the block count — a constant — not the shard
count, which is what makes ``shards=N`` outputs bit-identical to
``shards=1`` (the acceptance gate bench/tests check with
`np.array_equal`, not allclose).

**Generic dense path** (`ShardedBackend`): any `ModelBundle`-style
``fn(params, *inputs)`` serves sharded by storing its params through
`parallel/mesh.py`'s `shard_params` (megatron column/row rules,
`_clip_spec` replicating what doesn't divide) and reconstructing each
sharded leaf with a tiled `all_gather` inside the `shard_map` body
before running the unmodified fn — weight *storage* is partitioned
(the HBM win), the math is the original fn on bit-identical gathered
weights, so outputs are bit-identical to the unsharded backend for ANY
model. The LLM path above is the compute-partitioned specialization
for the transformer family.

**Placement composition** (`ShardedReplicaSet`): ``devices=M
shards=N`` stands up M/N shard *groups*, each group one logical
replica in the ReplicaSet routing/conservation machinery. Each group
leases its N chips from a `ChipLeaseTable` under one owner; fencing
ANY member chip fences the whole group (an SPMD program cannot run on
N-1 chips), the group's queued work re-routes to surviving groups via
the ReplicaSet reoffer path, and the conservation ledger
offered == admitted + Σrejected / admitted == replied + … stays exact.
Store hot swap generalizes unchanged: the group's one backend is one
store handle, its pre-warm compiles the N-chip SPMD executable — all
shards warm in one all-or-none step before the entry's single epoch
flip.

Long-context prefill can route through `parallel/ring_attention.py`
(`ring_prefill_min` tokens threshold): the sequence axis shards over
the same chips re-axed as ``sp`` and K/V blocks rotate by `ppermute`.
Ring attention's online softmax reassociates by design, so that path
is equivalent-math (tested allclose), not bit-exact — the parity gate
always runs the blocked path.

This module and `parallel/` are the only places allowed to construct
`shard_map` / `NamedSharding` / `PartitionSpec` (nnlint NNL012) —
sharding decisions cannot leak into random call sites.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.core.log import get_logger
from nnstreamer_tpu.serving.placement import (
    ChipLeaseTable, ReplicaSet, visible_devices)

log = get_logger("serving.sharding")

#: canonical block count: numerics depend on this constant, never on
#: the shard count, so any N dividing it serves bit-identical outputs
FIXED_BLOCKS = 8

#: shard counts the blocked layout supports (divisors of FIXED_BLOCKS)
SUPPORTED_SHARDS = (1, 2, 4, 8)


def tp_mesh(devices):
    """A 1-axis ("tp",) mesh over exactly these devices."""
    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), ("tp",))


def _sp_mesh(devices):
    """The same chips re-axed as ("sp",) for ring-attention prefill."""
    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), ("sp",))


def shard_devices(indices: Sequence[int]) -> list:
    """Device objects for a shard group's chip ordinals (routes through
    the placement subsystem's blessed enumeration, NNL009)."""
    devs = visible_devices()
    for i in indices:
        if not 0 <= int(i) < len(devs):
            found = (f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
            hint = ("; to emulate more on the host platform run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                    if devs[0].platform == "cpu" else
                    "; lower shards/devices to what this host has")
            raise BackendError(
                f"shard group wants device {i} but found {found}{hint}")
    return [devs[int(i)] for i in indices]


def validate_shards(n: int) -> int:
    n = int(n)
    if n not in SUPPORTED_SHARDS:
        raise BackendError(
            f"shards={n}: supported counts are {SUPPORTED_SHARDS} "
            f"(divisors of the canonical block count {FIXED_BLOCKS})")
    return n


# ---------------------------------------------------------------------------
# Generic dense path: sharded weight storage, gather-on-use compute
# ---------------------------------------------------------------------------

def dense_shard_rules():
    """Megatron column/row rules for generic dense params, layered over
    `parallel/mesh.default_param_rules` (conv patterns) with 2-D matmul
    weights column-split (`w1`-style names shard the output axis, `w2`/
    `wo`/`wd` the input axis). `_clip_spec` replicates anything the
    mesh doesn't divide — sharding never changes which model serves."""
    from jax.sharding import PartitionSpec as P

    from nnstreamer_tpu.parallel.mesh import default_param_rules

    return (
        ("w1", P(None, "tp")),
        ("wi", P(None, "tp")),
        ("wqkv", P(None, "tp")),
        ("w2", P("tp", None)),
        ("wo", P("tp", None)),
        ("wd", P("tp", None)),
    ) + tuple(default_param_rules())


def _gather_spec(x, spec):
    """all_gather a local leaf back to its global value, tiled along the
    (single) sharded axis; replicated leaves pass through."""
    import jax

    axes = [i for i, a in enumerate(spec) if a is not None]
    if not axes:
        return x
    return jax.lax.all_gather(x, "tp", axis=axes[0], tiled=True)


class ShardedBackend:
    """One model served by one N-chip SPMD program (the dense path).

    Holds params sharded across the group's mesh (`shard_params` +
    megatron rules); each invoke runs a `shard_map` whose body gathers
    the sharded leaves and applies the *unmodified* model fn — outputs
    are bit-identical to the single-chip backend by construction, and
    each chip stores only its 1/N slice of the split weights.

    Store integration mirrors the XLA backend's handle protocol:
    `prewarm_version` compiles the incoming version's N-chip executable
    for every served input signature BEFORE the store's epoch flip (one
    compile covers all shards — the all-or-none pre-warm is inherent to
    SPMD), `maybe_adopt` flips to the prepared version at the next
    invoke, and a flip after pre-warm costs zero recompiles.
    """

    def __init__(self, model, device_indices: Sequence[int], *,
                 name: str = "sharded"):
        self.name = name
        self.device_indices = tuple(int(i) for i in device_indices)
        self.shards = validate_shards(len(self.device_indices))
        self.mesh = tp_mesh(shard_devices(self.device_indices))
        self.compile_count = 0
        self.invokes = 0
        self.invoke_failures = 0
        self.adopted_epoch = -1
        self.swap_count = 0
        self._lock = threading.Lock()
        #: (version, shape-sig…) → jitted N-chip executable
        self._jits: Dict[tuple, Any] = {}
        #: version → {placed, specs, fn, host_pre}
        self._vers: Dict[Any, dict] = {}
        self._entry = None
        self._pinned = None
        self._version: Any = None
        self._bind(model)

    # -- model binding ------------------------------------------------------
    def _bind(self, model) -> None:
        if isinstance(model, str) and model.startswith("store://"):
            from nnstreamer_tpu.serving.store import (
                get_store, parse_store_ref)

            ref = parse_store_ref(model)
            self._entry = get_store().entry(ref.name)
            if ref.version is not None:
                self._pinned = self._entry.resolve_version(ref.version)
                self._version = self._pinned
            else:
                cur, epoch = self._entry.state
                self._version, self.adopted_epoch = cur, epoch
            if self._version is None:
                raise BackendError(
                    f"sharded backend: store model {ref.name!r} has no "
                    f"versions registered")
            self._vers[self._version] = self._place(
                self._entry.bundle(self._version))
            self._entry.attach(self)
            return
        # anything else (zoo://, ModelBundle, callables, file paths)
        # resolves through the XLA backend's blessed model resolution
        from nnstreamer_tpu.backends.xla import XLABackend

        self._version = None
        self._vers[None] = self._place(XLABackend()._resolve(model))

    def _place(self, bundle) -> dict:
        """Shard a version's params across the group mesh."""
        from nnstreamer_tpu.parallel.mesh import param_specs, shard_params

        rules = dense_shard_rules()
        params = bundle.params
        return {
            "placed": shard_params(params, self.mesh, rules),
            "specs": param_specs(params, self.mesh, rules),
            "fn": bundle.fn,
            "host_pre": getattr(bundle, "host_pre", None),
        }

    @property
    def tracks_store_epoch(self) -> bool:
        return self._entry is not None and self._pinned is None

    # -- store handle protocol ---------------------------------------------
    def maybe_adopt(self) -> None:
        if not self.tracks_store_epoch:
            return
        cur, epoch = self._entry.state
        if epoch == self.adopted_epoch:
            return
        with self._lock:
            if cur not in self._vers:        # flip without pre-warm
                self._vers[cur] = self._place(self._entry.bundle(cur))
            for v in [v for v in self._vers
                      if v not in (cur, self._pinned)]:
                del self._vers[v]
            for k in [k for k in self._jits
                      if k[0] not in (cur, self._pinned)]:
                del self._jits[k]
            self._version, self.adopted_epoch = cur, epoch
            self.swap_count += 1
        log.info("sharded %s adopted %s@%s epoch=%d", self.name,
                 self._entry.name, cur, epoch)

    def prewarm_version(self, version, bundle) -> int:
        """Swap-controller hook: shard the incoming version's params and
        compile its N-chip executable for every input signature this
        group has served — one SPMD compile warms every shard, so the
        store's epoch flip is all-or-none across the whole group by
        construction (any failure raises here, before the flip)."""
        with self._lock:
            self._vers[version] = self._place(bundle)
            served = sorted({k[1:] for k in self._jits})
        compiled = 0
        for sig in served:
            _, fresh = self._get_jit(sig, version)
            if fresh:
                # a real dummy invocation populates the dispatch cache
                # so the first post-flip invoke is a hit, not a compile
                dummy = tuple(np.zeros(s, d) for s, d in sig)
                self._run(dummy, version)
                compiled += 1
        return compiled

    # -- execution ----------------------------------------------------------
    def _sig(self, inputs: tuple) -> tuple:
        return tuple((tuple(np.shape(a)), np.asarray(a).dtype.str)
                     for a in inputs)

    def _get_jit(self, sig: tuple, version) -> Tuple[Any, bool]:
        import jax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        key = (version,) + tuple(sig)
        with self._lock:
            jitted = self._jits.get(key)
        if jitted is not None:
            return jitted, False
        ver = self._vers[version]
        specs, fn = ver["specs"], ver["fn"]
        narg = len(sig)

        def body(params, *inputs):
            full = jax.tree_util.tree_map(_gather_spec, params, specs)
            out = fn(full, *inputs)
            return tuple(out) if isinstance(out, (tuple, list)) else (out,)

        smapped = shard_map(
            body, mesh=self.mesh,
            in_specs=(specs,) + (P(),) * narg,
            out_specs=P(), check_vma=False)
        jitted = jax.jit(smapped)
        with self._lock:
            self._jits[key] = jitted
            self.compile_count += 1
        return jitted, True

    def _run(self, inputs: tuple, version):
        # inputs here are post-host_pre: sigs (and prewarm dummies built
        # from them) always describe what the device fn actually sees
        jitted, _ = self._get_jit(self._sig(inputs), version)
        return jitted(self._vers[version]["placed"], *inputs)

    def invoke(self, inputs: tuple) -> tuple:
        self.maybe_adopt()
        try:
            pre = self._vers[self._version]["host_pre"]
            if pre is not None:
                inputs = pre(tuple(inputs))
            out = self._run(tuple(inputs), self._version)
        except BackendError:
            self.invoke_failures += 1
            raise
        self.invokes += 1
        return tuple(np.asarray(o) for o in out)

    def invoke_batched(self, inputs: tuple, n: int, keepdims) -> tuple:
        # the group serves the stacked batch as one SPMD invocation —
        # batching semantics (stack axis, keepdims) are the caller's
        return self.invoke(inputs)

    # -- lifecycle ----------------------------------------------------------
    def warm_start(self) -> None:
        return None

    def close(self) -> None:
        if self._entry is not None:
            try:
                self._entry.detach(self)
            except Exception:
                pass
        with self._lock:
            self._jits.clear()
            self._vers.clear()

    def stats(self) -> dict:
        return {
            "devices": list(self.device_indices),
            "shards": self.shards,
            "invokes": self.invokes,
            "compile_count": self.compile_count,
            "adopted_epoch": self.adopted_epoch,
            "swap_count": self.swap_count,
        }


# ---------------------------------------------------------------------------
# Blocked transformer math (the paged-LLM TP path)
# ---------------------------------------------------------------------------

def blocked_transformer_params(params, *, n_heads: int):
    """Re-pack transformer params (models/transformer.init_params
    layout) into the canonical blocked layout.

    Per block b of FIXED_BLOCKS: wq/wk/wv hold head-block b's
    projection columns, wg/wu the SwiGLU gate/up feature block, wo/wd
    the matching row block, head the vocab column block. Every blocked
    array carries the block axis leading — `(8, …)` — which is the
    axis `shard_llm_params` puts on the ``tp`` mesh axis. Norm vectors
    and the embedding stay whole (replicated).
    """
    import jax.numpy as jnp

    B = FIXED_BLOCKS
    d = int(params["embed"].shape[1])
    vocab = int(params["head"].shape[1])
    hd = d // n_heads
    kv_dim = (int(params["blocks"][0]["wqkv"].shape[1]) - d) // 2
    n_kv = kv_dim // hd
    d_ff = int(params["blocks"][0]["wd"].shape[0])
    for nm, v in (("n_heads", n_heads), ("n_kv_heads", n_kv),
                  ("d_ff", d_ff), ("vocab", vocab)):
        if v % B:
            raise BackendError(
                f"shards=N needs {nm}={v} divisible by the canonical "
                f"block count {B} (models/transformer.init_params "
                f"geometry)")

    def cols(w):
        # (d, out) → (B, d, out/B) column blocks
        return jnp.asarray(w).reshape(w.shape[0], B, -1).transpose(1, 0, 2)

    def rows(w):
        # (in, d) → (B, in/B, d) row blocks
        return jnp.asarray(w).reshape(B, -1, w.shape[1])

    if "wqkv_scale" in params["blocks"][0]:
        raise BackendError(
            "sharded serving is float-only: W8A8-quantized store "
            "versions cannot re-block (per-column scales would split); "
            "serve quantized models unsharded")
    blocks = []
    for blk in params["blocks"]:
        wqkv = jnp.asarray(blk["wqkv"])
        wq, wk, wv = (wqkv[:, :d], wqkv[:, d:d + kv_dim],
                      wqkv[:, d + kv_dim:])
        wi = jnp.asarray(blk["wi"])
        wg, wu = wi[:, :d_ff], wi[:, d_ff:]
        blocks.append({
            "ln1": jnp.asarray(blk["ln1"]),
            "wq": cols(wq), "wk": cols(wk), "wv": cols(wv),
            "wo": rows(blk["wo"]),
            "ln2": jnp.asarray(blk["ln2"]),
            "wg": cols(wg), "wu": cols(wu),
            "wd": rows(blk["wd"]),
        })
    return {
        "embed": jnp.asarray(params["embed"]),
        "blocks": blocks,
        "ln_f": jnp.asarray(params["ln_f"]),
        "head": cols(jnp.asarray(params["head"])),
    }


def llm_shard_rules():
    """Blocked-layout rules: the leading block axis shards over tp."""
    from jax.sharding import PartitionSpec as P

    blocked = P("tp", None, None)
    return (
        ("wq", blocked), ("wk", blocked), ("wv", blocked),
        ("wg", blocked), ("wu", blocked),
        ("wo", blocked), ("wd", blocked),
        ("head", blocked),
        ("", P()),
    )


def shard_llm_params(params, mesh, *, n_heads: int):
    """Blocked re-pack + placement: returns (device pytree, spec
    pytree) for use as shard_map in_specs / jit arguments."""
    from nnstreamer_tpu.parallel.mesh import param_specs, shard_params

    blocked = blocked_transformer_params(params, n_heads=n_heads)
    rules = llm_shard_rules()
    return (shard_params(blocked, mesh, rules),
            param_specs(blocked, mesh, rules))


def kv_pool_specs():
    """PartitionSpec for the paged pools: the kv-head axis of
    (L, num_blocks, block_size, n_kv, hd) shards over tp, next to the
    head-blocked projections that read and write it."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, "tp", None)


def kv_pool_placer(mesh):
    """Placement hook for `PagedKVCache(placer=…)`: device_put the
    pools with the head-axis sharding (spec construction stays here —
    NNL012)."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, kv_pool_specs())

    def place(pool):
        return jax.device_put(pool, sharding)

    return place


def _combine_rows(parts, axis_name: str = "tp"):
    """Row-parallel combine with N-independent numerics: stack the
    local blocks' partial sums, all_gather into global (8, …) block
    order, reduce by a fixed-order chain of adds. A `psum` here would
    tie the reduction order to the shard count and break bit-parity."""
    import jax
    import jax.numpy as jnp

    part = jnp.stack(parts)                               # (8/N, …)
    allp = jax.lax.all_gather(part, axis_name, tiled=False)
    allp = allp.reshape((FIXED_BLOCKS,) + part.shape[1:])
    acc = allp[0]
    for i in range(1, FIXED_BLOCKS):
        acc = acc + allp[i]
    return acc


def _concat_cols(parts, axis_name: str = "tp"):
    """Column-parallel combine: gather the local blocks and concatenate
    along the feature axis in global block order (exact — pure data
    movement)."""
    import jax
    import jax.numpy as jnp

    part = jnp.stack(parts)                               # (8/N, …, f/8)
    allp = jax.lax.all_gather(part, axis_name, tiled=False)
    allp = allp.reshape((FIXED_BLOCKS,) + part.shape[1:])
    return jnp.concatenate([allp[i] for i in range(FIXED_BLOCKS)], axis=-1)


def _blocked_mlp(blk, x, dtype):
    """SwiGLU with per-block gate/up/down — block b's activation slice
    never touches another block's columns, so the only cross-shard op
    is the final fixed-order row combine."""
    import jax

    nloc = blk["wg"].shape[0]
    parts = []
    for j in range(nloc):
        gate = x @ blk["wg"][j].astype(dtype)
        up = x @ blk["wu"][j].astype(dtype)
        parts.append((jax.nn.silu(gate) * up) @ blk["wd"][j].astype(dtype))
    return _combine_rows(parts)


def sharded_paged_decode_step(params, cur, tables, pos, k_pool, v_pool,
                              *, n_heads=4, dtype=None):
    """Blocked-TP twin of `llm/paged_model.paged_decode_step`, written
    against LOCAL shards (runs inside shard_map; `make_llm_jits` wires
    the specs). Per local head-block: project q/k/v, rope, scatter this
    step's K/V into the LOCAL pool slice, attend through the block
    tables, partial-project through wo — then one fixed-order row
    combine per layer. Attention is per-head math, so head blocks never
    communicate; the pool never leaves its shard."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    b = cur.shape[0]
    _, _, block_size, n_kv_loc, hd = k_pool.shape
    max_blocks = tables.shape[1]
    kv_len = max_blocks * block_size
    rows = jnp.arange(b)
    write_blk = tables[rows, pos // block_size]
    write_off = pos % block_size
    nloc = params["blocks"][0]["wq"].shape[0]      # local head blocks
    kv_per_blk = n_kv_loc // nloc
    x = params["embed"][cur][:, None, :].astype(dtype)
    mask = (jnp.arange(kv_len)[None, None, None, :] <=
            pos[:, None, None, None])
    from nnstreamer_tpu.llm.parts import rope_rows
    from nnstreamer_tpu.models.transformer import rmsnorm

    for li, blk in enumerate(params["blocks"]):
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        hpb = blk["wq"].shape[2] // hd            # q heads per block
        parts = []
        for j in range(nloc):
            q = (h @ blk["wq"][j].astype(dtype)).reshape(b, 1, hpb, hd)
            k = (h @ blk["wk"][j].astype(dtype)).reshape(
                b, 1, kv_per_blk, hd)
            v = (h @ blk["wv"][j].astype(dtype)).reshape(
                b, 1, kv_per_blk, hd)
            q, k = rope_rows(q, pos), rope_rows(k, pos)
            kvs = slice(j * kv_per_blk, (j + 1) * kv_per_blk)
            k_pool = k_pool.at[li, write_blk, write_off, kvs].set(
                k[:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[li, write_blk, write_off, kvs].set(
                v[:, 0].astype(v_pool.dtype))
            kc = k_pool[li][:, :, kvs][tables].reshape(
                b, kv_len, kv_per_blk, hd)
            vc = v_pool[li][:, :, kvs][tables].reshape(
                b, kv_len, kv_per_blk, hd)
            kcx = jnp.repeat(kc, hpb // kv_per_blk,
                             axis=2).astype(jnp.float32)
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           kcx) * hd ** -0.5
            s = jnp.where(mask, s, -1e30)
            pattn = jax.nn.softmax(s, axis=-1)
            vcx = jnp.repeat(vc, hpb // kv_per_blk,
                             axis=2).astype(jnp.float32)
            attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vcx).astype(dtype)
            parts.append(attn.reshape(b, 1, -1) @ blk["wo"][j].astype(dtype))
        x = x + _combine_rows(parts)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + _blocked_mlp(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    nhb = params["head"].shape[0]
    logits = _concat_cols(
        [x[:, 0] @ params["head"][j].astype(dtype) for j in range(nhb)])
    return logits.astype(jnp.float32), k_pool, v_pool


def sharded_paged_prefill(params, ids, blk_idx, blk_off, k_pool, v_pool,
                          last_idx, *, n_heads=4, dtype=None):
    """Blocked-TP twin of `paged_prefill`: full-sequence causal forward
    + per-shard KV scatter, per local head-block. Same canonical
    blocking as the decode step, so ``shards=N`` prefill logits (and
    the KV every later decode reads) are bit-identical to ``shards=1``.
    Returns (last-token logits (vocab,), k_pool, v_pool)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    s_len = ids.shape[1]
    _, _, _, n_kv_loc, hd = k_pool.shape
    nloc = params["blocks"][0]["wq"].shape[0]
    kv_per_blk = n_kv_loc // nloc
    pos = jnp.arange(s_len)
    causal = (jnp.arange(s_len)[None, :] <=
              jnp.arange(s_len)[:, None])[None, None, :, :]
    x = params["embed"][ids].astype(dtype)                # (1, S, D)
    from nnstreamer_tpu.models.transformer import rmsnorm, rope

    for li, blk in enumerate(params["blocks"]):
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        hpb = blk["wq"].shape[2] // hd
        parts = []
        for j in range(nloc):
            q = (h @ blk["wq"][j].astype(dtype)).reshape(
                1, s_len, hpb, hd)
            k = (h @ blk["wk"][j].astype(dtype)).reshape(
                1, s_len, kv_per_blk, hd)
            v = (h @ blk["wv"][j].astype(dtype)).reshape(
                1, s_len, kv_per_blk, hd)
            q, k = rope(q, pos), rope(k, pos)
            kvs = slice(j * kv_per_blk, (j + 1) * kv_per_blk)
            k_pool = k_pool.at[li, blk_idx, blk_off, kvs].set(
                k[0].astype(k_pool.dtype))
            v_pool = v_pool.at[li, blk_idx, blk_off, kvs].set(
                v[0].astype(v_pool.dtype))
            kcx = jnp.repeat(k, hpb // kv_per_blk,
                             axis=2).astype(jnp.float32)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            kcx) * hd ** -0.5
            sc = jnp.where(causal, sc, -1e30)
            pattn = jax.nn.softmax(sc, axis=-1)
            vcx = jnp.repeat(v, hpb // kv_per_blk,
                             axis=2).astype(jnp.float32)
            attn = jnp.einsum("bhqk,bkhd->bqhd", pattn, vcx).astype(dtype)
            parts.append(
                attn.reshape(1, s_len, -1) @ blk["wo"][j].astype(dtype))
        x = x + _combine_rows(parts)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + _blocked_mlp(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    nhb = params["head"].shape[0]
    logits = _concat_cols(
        [x[0] @ params["head"][j].astype(dtype) for j in range(nhb)])
    return (logits.astype(jnp.float32)[last_idx], k_pool, v_pool)


def make_llm_fns(mesh, param_spec_tree, mesh_devices=None):
    """Unjitted N-chip callables for the sharded paged family, keyed by
    kind — what `PagedLLMExecutor` jits per (namespace, kind, bucket)
    under its ``("tp", N, …)`` namespace, preserving its per-bucket
    compile accounting. Signatures mirror `llm/paged_model.py`
    (params, …, k_pool, v_pool → (logits, k_pool, v_pool)); the pools
    stay head-sharded in and out (donated by the executor's jit).

    "ring" is the long-context prefill twin: `ring_prefill` attention
    (sequence-parallel over the same chips) + the standard pool
    scatter. It takes RAW (unblocked, replicated) params — see
    `replicate_params` — and is allclose-, not bit-, equivalent."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    pool = kv_pool_specs()

    def prefill(params, ids, blk_idx, blk_off, k_pool, v_pool,
                last_idx, n_heads=4, dtype=None):
        body = shard_map(
            lambda p, i, bi, bo, kp, vp, la: sharded_paged_prefill(
                p, i, bi, bo, kp, vp, la, n_heads=n_heads, dtype=dtype),
            mesh=mesh,
            in_specs=(param_spec_tree, P(), P(), P(), pool, pool, P()),
            out_specs=(P(), pool, pool), check_vma=False)
        return body(params, ids, blk_idx, blk_off, k_pool, v_pool,
                    last_idx)

    def decode(params, cur, tables, pos, k_pool, v_pool,
               n_heads=4, dtype=None):
        body = shard_map(
            lambda p, c, t, q, kp, vp: sharded_paged_decode_step(
                p, c, t, q, kp, vp, n_heads=n_heads, dtype=dtype),
            mesh=mesh,
            in_specs=(param_spec_tree, P(), P(), P(), pool, pool),
            out_specs=(P(), pool, pool), check_vma=False)
        return body(params, cur, tables, pos, k_pool, v_pool)

    devs = (list(mesh_devices) if mesh_devices is not None
            else list(mesh.devices.flat))

    def ring(params, ids, blk_idx, blk_off, k_pool, v_pool,
             last_idx, n_heads=4, dtype=None):
        logits, ks, vs = ring_prefill(params, ids, devs,
                                      n_heads=n_heads, dtype=dtype)
        # standard paged_prefill scatter; the head-sharded pool writes
        # partition under GSPMD (replicated ks/vs → local head slices)
        k_pool = k_pool.at[:, blk_idx, blk_off].set(
            ks[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[:, blk_idx, blk_off].set(
            vs[:, 0].astype(v_pool.dtype))
        return logits[0, last_idx], k_pool, v_pool

    return {"prefill": prefill, "decode": decode, "ring": ring}


def make_llm_jits(mesh, param_spec_tree):
    """Jitted convenience wrappers over `make_llm_fns` (tests/bench) —
    same static/donate discipline as the executor's per-bucket jits:
    pools donate (write-in-place on device), n_heads/dtype static."""
    import jax

    fns = make_llm_fns(mesh, param_spec_tree)
    return {
        "prefill": jax.jit(fns["prefill"],
                           static_argnames=("n_heads", "dtype"),
                           donate_argnums=(4, 5)),
        "decode": jax.jit(fns["decode"],
                          static_argnames=("n_heads", "dtype"),
                          donate_argnums=(4, 5)),
    }


def replicate_params(params, mesh):
    """device_put a raw params pytree fully replicated across the group
    mesh (the ring-prefill path serves the unblocked weights; spec
    construction stays here — NNL012)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), params)


def ring_prefill(params, ids, mesh_devices, *, n_heads=4, dtype=None):
    """Long-context prefill attention via `parallel/ring_attention.py`:
    the same chips re-axed as ("sp",), sequence sharded, K/V rotating
    by ppermute. Returns (logits (1,S,vocab) f32, ks, vs) with ks/vs
    (L, 1, S, n_kv, hd) — `paged_prefill`'s KV layout, for scatter into
    the (sharded) pools. Online-softmax math: equivalent to the blocked
    path within float tolerance, never bit-exact — callers gate it on a
    length threshold and the parity tests pin the blocked path."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.transformer import (
        expand_kv, _qkv, _mlp, rmsnorm, rope)
    from nnstreamer_tpu.parallel.ring_attention import ring_attention

    dtype = dtype or jnp.float32
    mesh = _sp_mesh(mesh_devices)
    b, s = ids.shape
    if s % max(1, len(mesh_devices)):
        raise BackendError(
            f"ring prefill needs the bucketed prompt length ({s}) "
            f"divisible by the shard count ({len(mesh_devices)})")
    x = params["embed"][ids].astype(dtype)
    pos = jnp.arange(s)
    ks, vs = [], []
    for blk in params["blocks"]:
        h = rmsnorm(x, blk["ln1"].astype(dtype))
        q, k, v = _qkv(blk, h, n_heads, dtype)
        q, k = rope(q, pos), rope(k, pos)
        ks.append(k)
        vs.append(v)
        attn = ring_attention(q, expand_kv(k, n_heads),
                              expand_kv(v, n_heads), mesh=mesh,
                              axis="sp", causal=True)
        x = x + attn.reshape(b, s, -1) @ blk["wo"].astype(dtype)
        h = rmsnorm(x, blk["ln2"].astype(dtype))
        x = x + _mlp(blk, h, dtype)
    x = rmsnorm(x, params["ln_f"].astype(dtype))
    logits = (x @ params["head"].astype(dtype)).astype(jnp.float32)
    return logits, jnp.stack(ks), jnp.stack(vs)


# ---------------------------------------------------------------------------
# Shard groups: placement + routing + fencing
# ---------------------------------------------------------------------------

class ShardedReplicaSet(ReplicaSet):
    """G shard groups of N chips each behind the ReplicaSet front door.

    Each "replica" is one `ShardedBackend` — an N-chip SPMD program —
    so routing, backpressure, the reoffer path and the conservation
    ledger are inherited unchanged; what changes is the failure unit:
    `fence_device(chip)` fences the chip's whole GROUP (SPMD cannot run
    on N-1 chips), its lease rows flip to fenced in the group's
    `ChipLeaseTable`, and the stranded work re-routes to surviving
    groups exactly like a fenced dp replica's."""

    def __init__(self, backends, group_devices: List[Tuple[int, ...]],
                 leases: Optional[ChipLeaseTable] = None, **kw):
        self.group_devices = [tuple(g) for g in group_devices]
        self.leases = leases
        super().__init__(backends, list(range(len(backends))), **kw)

    @classmethod
    def open_sharded(cls, model, *, shards: int, groups: int = 0,
                     leases: Optional[ChipLeaseTable] = None,
                     queue_cap: int = 64, name: str = "sharded",
                     tracer=None) -> "ShardedReplicaSet":
        """Stand up `groups` shard groups of `shards` chips (0 = as
        many as the visible device count fits, at least one). Chips are
        leased per group from `leases` (one owner per group, so a group
        fence is one ledger fence) — a fresh table over the visible
        devices when the caller does not share one."""
        shards = validate_shards(shards)
        ndev = len(visible_devices())
        if groups <= 0:
            groups = max(1, ndev // shards)
        if groups * shards > ndev:
            raise BackendError(
                f"shards={shards} x {groups} groups needs "
                f"{groups * shards} devices, {ndev} visible; run under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
        if leases is None:
            leases = ChipLeaseTable(range(ndev))
        store_name = ""
        if isinstance(model, str) and model.startswith("store://"):
            store_name = model[len("store://"):].split("@", 1)[0]
        backends, group_devs = [], []
        try:
            for g in range(groups):
                chips = leases.lease(f"{name}/g{g}", shards)
                b = ShardedBackend(model, chips, name=f"{name}/g{g}")
                backends.append(b)
                group_devs.append(chips)
        except Exception:
            for g, b in enumerate(backends):
                try:
                    b.close()
                except Exception:
                    pass
                leases.release(f"{name}/g{g}")
            raise
        return cls(backends, group_devs, leases, queue_cap=queue_cap,
                   bucket=1, name=name, tracer=tracer,
                   store_name=store_name)

    # -- group fencing ------------------------------------------------------
    def group_of(self, chip: int) -> Optional[int]:
        for g, devs in enumerate(self.group_devices):
            if int(chip) in devs:
                return g
        return None

    def fence_device(self, chip: int, cause: str = "fenced") -> bool:
        """A member chip died: fence its whole shard group — the lease
        rows AND the routing replica — so conservation flows through
        the inherited reoffer path."""
        g = self.group_of(chip)
        if g is None:
            return False
        if self.leases is not None:
            self.leases.fence(f"{self.name}/g{g}")
        return self.fence(g, f"member chip {chip} {cause}")

    # -- stats --------------------------------------------------------------
    def stats(self) -> dict:
        out = super().stats()
        # rows stay under "replicas" — filter.extra_stats and the metric
        # scrape read that key; sharded-ness is extra fields, not a new
        # schema
        for g, row in enumerate(out["replicas"]):
            row["group"] = g
            row["devices"] = list(self.group_devices[g])
            row["shards"] = len(self.group_devices[g])
        out["group_size"] = (len(self.group_devices[0])
                             if self.group_devices else 0)
        if self.leases is not None:
            out["leases"] = self.leases.snapshot()["counts"]
        return out
