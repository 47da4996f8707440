"""Continuous-batching LLM serving (docs/llm_serving.md).

The prefill/decode split and the streaming transformer
(models/transformer.py) become a first-class serving workload:

- `paged_cache`  — fixed-size-block KV pool + free-list allocator, so
  slot count (not max_len × batch) bounds HBM.
- `paged_model`  — prefill/decode math over the paged pool, formulated
  for token-for-token parity with `transformer.generate`: the dense
  family; `sparse_moe`, `hybrid_lm`, `window_moe`, `latent_moe`,
  `delta_moe` the others.
- `parts`, `experts` — what two or more families use: no family module
  imports another (but `delta_moe`, whose latent layers are `latent_moe`'s
  whole layers under public names).
- `families`     — one program set a model family (`spec.LMSpec.family`):
  which programs serve it, their arguments, refusals and counters.
- `engine`       — the continuous-batching scheduler loop: admit,
  prefill (pow2-bucketed), merge into the in-flight decode batch,
  retire; plus the static-batching A/B mode the bench compares against.

`elements/llm.py` exposes the engine as the `tensor_llm` pipeline
element; `backends/llm_exec.py` owns the bucketed, version-namespaced
jits underneath it.
"""

from nnstreamer_tpu.llm.engine import LLMEngine, LLMRequest  # noqa: F401
from nnstreamer_tpu.llm.paged_cache import (  # noqa: F401
    BlockAllocator, PagedKVCache)

__all__ = ["BlockAllocator", "LLMEngine", "LLMRequest", "PagedKVCache"]
