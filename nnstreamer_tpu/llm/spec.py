"""One description of a language model, carried by its bundle.

`ModelBundle.lm` holds an `LMSpec` when the parameters alone cannot say
what they are: the family, and the sizes a params pytree does not spell
out (a query width that is not the hidden size, a rope base, an indexer,
experts, the kinds of its layers). `PagedLLMExecutor` reads its dims
and `n_heads` from the spec when the bundle has one, and from the
parameters' shapes and the element's `n_heads` property, as it always
did, when it has none.
`family` picks the model's program set from `llm/families.py`'s table.

Frozen and hashable: the sparse-expert and hybrid programs take it as a
static argument of their jits.
"""

from __future__ import annotations

from dataclasses import dataclass

#: the pre-norm rotary SwiGLU decoder with a fused wqkv whose query width
#: is the hidden size (models/transformer.py, llm/paged_model.py)
DENSE = "dense"
#: the sparse-expert decoder whose attention a learned indexer chooses
#: (llm/sparse_moe.py)
SPARSE_MOE = "sparse_moe"
#: the decoder whose layers are linear attention with a carried state or
#: block-sparse attention over paged KV (llm/hybrid_lm.py)
HYBRID = "hybrid"
#: the kinds of layer `LMSpec.layer_kinds` names
LINEAR, SPARSE = "linear", "sparse"


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
    # the hybrid family. The kind of each layer in order, LINEAR or
    # SPARSE (a tuple: the spec stays hashable); () = one kind of layer
    layer_kinds: tuple = ()
    # a LINEAR layer: lin_heads heads of head_dim keep a state of
    # head_dim x head_dim each, a sequence; rope on q and k
    lin_heads: int = 0
    # a SPARSE layer (n_heads query and n_kv key/value heads, no rope):
    # a compressed key is the mean of ck_kernel keys every ck_stride
    # tokens; a query attends sel_topk blocks of sel_block tokens, the
    # first sel_init blocks and the newest sel_window tokens' among them
    ck_kernel: int = 0
    ck_stride: int = 0
    sel_block: int = 0
    sel_topk: int = 0
    sel_window: int = 0
    sel_init: int = 0
    # the three scalings: the embedding is multiplied by emb_scale, each
    # residual branch by residual_scale, and the final norm's output is
    # divided by logit_div before the head
    emb_scale: float = 1.0
    residual_scale: float = 1.0
    logit_div: float = 1.0
