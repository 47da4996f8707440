"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

No reference counterpart (SURVEY.md §5.8 — the reference has no
collectives at all); this is the TPU-native sparse-capacity scale-out:
experts live on different devices, tokens travel to their expert and
back with two `lax.all_to_all` collectives over ICI.

Switch-transformer-style design (static shapes throughout — XLA needs
them, and so does the MXU):
- top-1 gating with a fixed per-expert capacity C; tokens over capacity
  are dropped from the expert path (their contribution is zero and the
  caller's residual connection carries them — standard Switch behavior);
- dispatch is a one-hot einsum into an (E, C, d) buffer, so routing is
  dense matmul work, not scatter;
- all_to_all #1 re-shards the buffer from token-owners to expert-owners
  (split the E dim, concat the sender dim); experts run as one batched
  einsum over their local expert group; all_to_all #2 reverses the
  exchange; a final one-hot einsum combines results back per token,
  scaled by the gate probability.

Tokens are sharded over ``ep`` too (each device both owns tokens and
hosts experts), which is what makes the exchange an all_to_all instead
of an all_gather.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def init_moe_params(key, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    kg, k1, k2 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return {
        "gate": (jax.random.normal(kg, (d_model, n_experts)) * s).astype(dtype),
        "w1": (jax.random.normal(k1, (n_experts, d_model, d_hidden)) * s
               ).astype(dtype),
        "w2": (jax.random.normal(k2, (n_experts, d_hidden, d_model))
               * d_hidden ** -0.5).astype(dtype),
    }


def moe_param_specs() -> Dict[str, P]:
    """Sharding rules: experts over ep, gate replicated."""
    return {"gate": P(), "w1": P("ep"), "w2": P("ep")}


def _route(x, gate_w, n_experts: int, capacity: int):
    """Top-1 routing for local tokens x: (t, d) →
    dispatch (t, E, C) one-hot, probs (t,)."""
    logits = x @ gate_w                                   # (t, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                   # (t,)
    p = jnp.max(probs, axis=-1)                           # (t,)
    onehot_e = jax.nn.one_hot(expert, n_experts, dtype=x.dtype)   # (t, E)
    # position of each token within its expert's buffer (arrival order).
    # Counting runs in int32 NO MATTER the activation dtype: a bf16
    # cumsum cannot represent integers above 256, which would collapse
    # distinct slots and silently sum two tokens into one buffer entry
    counts = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(counts, axis=0) - 1) * counts               # (t, E)
    pos_i = jnp.sum(pos, axis=-1)                                 # (t,) i32
    keep = (pos_i < capacity).astype(x.dtype)
    onehot_c = jax.nn.one_hot(pos_i, capacity, dtype=x.dtype)     # (t, C)
    dispatch = onehot_e[:, :, None] * onehot_c[:, None, :] \
        * keep[:, None, None]                                     # (t, E, C)
    return dispatch, p.astype(x.dtype)


def moe_apply(params, x, *, mesh: Mesh, axis: str = "ep",
              capacity_factor: float = 1.25):
    """Expert-parallel MoE layer. x: (T, d) with T sharded over `axis`;
    params per init_moe_params with w1/w2 sharded over `axis` dim 0.
    Returns (T, d), same sharding. Add the residual outside."""
    n = mesh.shape[axis]
    n_experts = params["w1"].shape[0]
    if n_experts % n:
        raise ValueError(
            f"{n_experts} experts do not divide over ep={n} devices")
    t_local = x.shape[0] // n
    capacity = max(1, math.ceil(capacity_factor * t_local / n_experts))

    def local(gate_w, w1, w2, xs):
        # xs: (t, d) local tokens; w1/w2: (E/n, ...) local expert group
        dispatch, p = _route(xs, gate_w, n_experts, capacity)
        buf = jnp.einsum("tec,td->ecd", dispatch, xs)     # (E, C, d)
        # token-owner → expert-owner exchange: (E, C, d) → (E/n, n·C, d)
        recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                              tiled=True)
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", recv, w1))
        y = jnp.einsum("ech,ehd->ecd", h, w2)             # (E/n, n·C, d)
        back = lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                              tiled=True)                 # (E, C, d)
        out = jnp.einsum("tec,ecd->td", dispatch, back)
        return out * p[:, None]

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )(params["gate"], params["w1"], params["w2"], x)


def reference_moe(params, x):
    """Serial ground truth (no capacity drops): every token goes to its
    argmax expert, scaled by the gate prob."""
    logits = x @ params["gate"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    p = jnp.max(probs, axis=-1).astype(x.dtype)
    h = jax.nn.gelu(jnp.einsum("td,edh->teh", x, params["w1"]))
    y = jnp.einsum("teh,ehd->ted", h, params["w2"])       # (t, E, d)
    sel = jnp.take_along_axis(
        y, expert[:, None, None].repeat(y.shape[-1], -1), axis=1)[:, 0]
    return sel * p[:, None]
