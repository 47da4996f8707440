"""One description of a language model, carried by its bundle.

`ModelBundle.lm` holds an `LMSpec` when the parameters alone cannot say
what they are: the family, and the sizes a params pytree does not spell
out (a query width that is not the hidden size, a rope base, an indexer,
experts). `PagedLLMExecutor` reads its dims and `n_heads` from the spec
when the bundle has one, and from the parameters' shapes and the
element's `n_heads` property, as it always did, when it has none.
`family` picks the model's program set from `llm/families.py`'s table.

Frozen and hashable: the sparse-expert programs take it as a static
argument of their jits.
"""

from __future__ import annotations

from dataclasses import dataclass

#: the pre-norm rotary SwiGLU decoder with a fused wqkv whose query width
#: is the hidden size (models/transformer.py, llm/paged_model.py)
DENSE = "dense"
#: the sparse-expert decoder whose attention a learned indexer chooses
#: (llm/sparse_moe.py)
SPARSE_MOE = "sparse_moe"


@dataclass(frozen=True)
class LMSpec:
    family: str = DENSE
    n_heads: int = 4
    n_kv: int = 4
    head_dim: int = 16
    rope_theta: float = 10000.0
    qk_norm: bool = False          # per-head RMSNorm on q and k
    # the indexer: idx_heads query heads and one key head of idx_dim,
    # picking the topk positions every query attends (0 = no indexer)
    idx_heads: int = 0
    idx_dim: int = 0
    topk: int = 0
    # the expert layer: n_experts of width expert_width, experts_per_tok
    # a token with renormalised weights (0 = a dense MLP)
    n_experts: int = 0
    experts_per_tok: int = 0
    expert_width: int = 0
