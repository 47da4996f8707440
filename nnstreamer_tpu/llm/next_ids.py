"""Greedy token ids that stay on the device (pure jax, called by llm_exec).

A decode step needs one int32 a row from the step before it: the index
of the largest logit. Taken on the device and kept there, it lets the
executor launch step n+1 before the host has read step n
(llm/engine.py), and the host reads `rows x 4` bytes of ids where it
read `rows x vocab x 4` of logits.

Where the ids live between two launches: `last` is one int32 a pool
block, and a sequence's last token sits at the index of the first block
of its table. Live sequences own distinct blocks, so they own distinct
entries; a freed block's entry is stale until the next owner's prefill
overwrites it, which the device runs after every program launched
before it. Padding rows of a decode bucket use the scratch block's
entry, as their KV writes use the scratch block.

`jnp.argmax` returns the lowest index among equal maxima, as `np.argmax`
does: the ids are the host sampler's at temperature 0, bit for bit.

Each program has one shape a decode bucket (`llm_pick_first`: one in
all), and none is named like a step program: the benchmark finds those
by the prefix of their names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@jax.jit
def llm_last_ids(last, tables, cur):
    """`cur` of a decode bucket: the host's `cur[i]`, or where that is
    negative (the host has not read row i's last token) the id kept for
    the row's table."""
    return jnp.where(cur < 0, last[tables[:, 0]], cur)


@functools.partial(jax.jit, donate_argnums=(1,))
def llm_pick_rows(logits, last, tables):
    """A decode step's ids (B_b,) int32, and `last` with each written
    at its row's first block."""
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return ids, last.at[tables[:, 0]].set(ids)


@functools.partial(jax.jit, donate_argnums=(1,))
def llm_pick_first(logits, last, slot):
    """A prefill's first id () int32 from its last position's logits
    (vocab,), and `last` with it written at `slot`, the first block of
    the prompt's table."""
    tok = jnp.argmax(logits).astype(jnp.int32)
    return tok, last.at[slot].set(tok)
