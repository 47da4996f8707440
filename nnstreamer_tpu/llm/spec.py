"""One description of a language model, carried by its bundle.

`ModelBundle.lm` holds an `LMSpec` when the parameters alone cannot say
what they are: the family, and the sizes a params pytree does not spell
out (a query width that is not the hidden size, a rope base, an indexer,
experts and which of them are held here, the kinds of its layers, a
window). `PagedLLMExecutor` reads its dims
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
#: the decoder whose layers attend a window of the newest positions or
#: the whole context, over a paged pool a kind, a dense MLP in its first
#: layers and then a shared expert beside this chip's share of the routed
#: experts (llm/window_moe.py)
WINDOW_MOE = "window_moe"
#: the decoder whose attention is latent: a token keeps one compressed
#: row and one roped key that all heads share, in a pool with no head axis
#: and no values; a dense MLP in its first layers and then shared experts
#: beside this chip's share of routed experts chosen inside groups
#: (llm/latent_moe.py)
LATENT_MOE = "latent_moe"
#: the decoder whose layers are gated delta-rule linear attention (a
#: float32 state and the tails of three short convolutions a sequence, by
#: slot) or latent attention with no rope and no query rank (a pool of
#: one compressed row a token, by block); a dense MLP in its first layers
#: and then a shared expert beside this chip's share of the routed experts
#: (llm/delta_moe.py)
DELTA_MOE = "delta_moe"
#: the kinds of layer `LMSpec.layer_kinds` names: the hybrid family's two,
#: the window family's two, the delta family's two
LINEAR, SPARSE = "linear", "sparse"
WINDOW, FULL = "window", "full"
KDA, LATENT = "kda", "latent"


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
    # the expert layer's router beyond softmax scores: "sigmoid" scores
    # each expert apart and chooses by score + the router's bias (which
    # is in the choice, not in the weights); the renormalised weights are
    # multiplied by route_scale
    score_fn: str = "softmax"
    route_scale: float = 1.0
    # the share of the routed experts held here: experts_held of them from
    # experts_first on (0 = all n_experts). The router scores all
    # n_experts; a pair routed to an expert that is not held adds nothing
    experts_first: int = 0
    experts_held: int = 0
    # the window family (layer_kinds of WINDOW and FULL). A WINDOW layer's
    # query at t attends the positions t - window < s <= t and is roped;
    # a FULL layer's attends all s <= t and is not. Both: q and k are
    # normed a head, the attention's output is multiplied by sigmoid(u Wg)
    # before Wo, each branch is normed before and after (ln1..ln4). The
    # first dense_layers layers have a SwiGLU of dense_width, the others
    # the expert layer beside a shared SwiGLU of shared_width every token
    # passes. norm_eps is every RMSNorm's
    window: int = 0
    dense_layers: int = 0
    dense_width: int = 0
    shared_width: int = 0
    norm_eps: float = 1e-6
    # the expert layer's router, further: n_group > 1 divides the experts
    # into groups of equal size, a group scores as its best expert, and
    # only the experts of the topk_group best groups can be chosen;
    # route_norm False leaves the chosen scores as they are (times
    # route_scale) and does not renormalise them
    n_group: int = 0
    topk_group: int = 0
    route_norm: bool = True
    # the latent family. The query goes through q_rank values and a norm
    # to n_heads heads of nope_dim + rope_dim; a token's key and value
    # through kv_rank values and a norm (the latent the pool keeps) to
    # n_heads heads of nope_dim + v_dim, beside one roped key of rope_dim
    # that all heads share. `dense_layers`, `dense_width`, `shared_width`
    # and `norm_eps` as above
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    # YaRN on the roped dims (rope_theta the base): positions past
    # yarn_orig_len are reached by dividing the slow frequencies by
    # yarn_factor (0 = plain rope), the ramp between yarn_beta_fast and
    # yarn_beta_slow turns of the original length; cos and sin are
    # multiplied by m(factor, mscale) / m(factor, mscale_all_dim) and the
    # scores by m(factor, mscale_all_dim)^2, m(s, a) = 0.1 a ln s + 1
    yarn_factor: float = 0.0
    yarn_orig_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 0.0
    yarn_mscale_all_dim: float = 0.0
    # q_rank 0: the query has no low-rank step, u Wq straight to the heads
    # (no norm). roped False: the rope_dim values of a query and of the
    # shared key ride unturned (no rotation anywhere)
    roped: bool = True
    # the delta family (layer_kinds of KDA and LATENT). A KDA layer:
    # lin_heads heads of head_dim; q, k and v each through a causal
    # depthwise convolution over conv_kernel tokens and SiLU (a sequence
    # carries the last conv_kernel - 1 inputs of each: its tails); q and k
    # L2-normed a head; a decay a channel and an output gate, each
    # through a low-rank pair whose rank the weights say; a state of
    # head_dim x head_dim a head, float32, updated by the delta rule. A
    # LATENT layer is the latent family's, with the fields above
    conv_kernel: int = 0
