"""Pipeline parallelism — GPipe-style microbatched execution over the
``pp`` mesh axis.

Nothing in the reference corresponds to this (its multi-device story is
per-frame TCP offload, SURVEY.md §5.8); this is the TPU-native way to
run a model deeper than one chip's HBM: stages live on different devices
and activations flow stage-to-stage over ICI.

Design (collective SPMD, not per-device programs):
- stage parameters are *stacked* on a leading stage dim and sharded over
  ``pp``, so inside `shard_map` every device holds exactly its stage's
  weights;
- the input is split into microbatches; a `fori_loop` runs the classic
  GPipe schedule: at step t, stage s computes microbatch (t - s), then
  every stage ships its activation to the next stage with one
  `lax.ppermute` (nearest-neighbor ICI hop);
- the bubble is (n_stages - 1) of (n_micro + n_stages - 1) steps — more
  microbatches amortize it;
- stages must be shape-preserving (activation shape constant across
  stages), the standard homogeneous-pipeline restriction.

The final outputs are collected on the last stage and `psum`-broadcast
so the caller gets a replicated array; a production serving path would
keep them on the last stage (donate into the next pipeline step).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim
    (what pipeline_apply expects, sharded P("pp") on dim 0)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params)


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "pp"):
    """Run x through n_stages of `stage_fn`, pipelined over `axis`.

    stage_fn(params, a) -> a  (shape-preserving)
    stage_params: pytree, every leaf (n_stages, ...), sharded over axis
    x: (n_micro, mb, ...) microbatched input, replicated over axis
    → (n_micro, mb, ...) outputs, replicated over axis.
    """
    n = mesh.shape[axis]
    n_micro = x.shape[0]
    if n_micro < 1:
        raise ValueError("pipeline_apply needs at least one microbatch")

    def local(params, xs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)  # my stage
        idx = lax.axis_index(axis)
        total = n_micro + n - 1
        state = jnp.zeros_like(xs[0])       # activation register from prev
        buf = jnp.zeros_like(xs)            # last stage's results

        def body(t, carry):
            state, buf = carry
            mb = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(idx == 0, xs[mb], state)
            y = stage_fn(params, x_in)
            # last stage owns microbatch t-(n-1) once the fill completes
            out_i = jnp.clip(t - (n - 1), 0, n_micro - 1)
            keep = (idx == n - 1) & (t >= n - 1)
            buf = buf.at[out_i].set(jnp.where(keep, y, buf[out_i]))
            # one ICI hop: every stage feeds the next (ring closes the
            # permutation; stage 0 ignores what it receives from n-1)
            state = lax.ppermute(y, axis,
                                 [(j, (j + 1) % n) for j in range(n)])
            return state, buf

        _, buf = lax.fori_loop(0, total, body, (state, buf))
        # broadcast the last stage's buffer to everyone (replicated out)
        return lax.psum(jnp.where(idx == n - 1, buf, jnp.zeros_like(buf)),
                        axis)

    # everything not named `axis` stays replicated in this collective;
    # callers compose dp outside (vmap/jit over a dp-sharded batch)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def reference_pipeline(stage_fn: Callable, per_stage_params, x):
    """Serial ground truth: fold the stages over every microbatch."""
    def one(mb):
        a = mb
        for p in per_stage_params:
            a = stage_fn(p, a)
        return a

    return jnp.stack([one(x[i]) for i in range(x.shape[0])], axis=0)
