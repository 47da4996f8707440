"""Kernels of the served path compiled for a described TPU v5e at the
sizes the benchmark's cells run them at: what Mosaic refuses (a block
that does not tile, too much fast memory) shows here and not on the chip.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, never at import (one worker
of several loads the TPU's library; docs in the on-chip-measurement
guide), and every such compile lives in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nnstreamer_tpu.backends import pallas_ops
from nnstreamer_tpu.llm import sparse_moe


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
# heads of 128, chunks of 2,048, a context of 33 tiles) and a short bucket
@pytest.mark.parametrize("c", [2048, 64])
def test_selected_block_update_compiles_at_the_cells_sizes(one_chip, c):
    nkv, grp, hd, tile, s_pad = 4, 8, 128, sparse_moe._CTX_TILE, 33792

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(q, k, v, keys, t, cut, j, m, l, acc):
        return pallas_ops.selected_block_update(
            q, k, v, keys, t, cut, j, m, l, acc,
            block_q=sparse_moe._FUSED_Q_BLOCK, interpret=False)

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
