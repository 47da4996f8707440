"""Runner of kind ``latent_moe_llm``: the ``window_moe_llm`` runner for the
latent family (``nnstreamer_tpu/llm/latent_moe.py``: one compressed row and
one roped key a token in a pool with no head axis and no values, read in
two forms; shared experts beside this chip's share of routed experts chosen
inside groups).  Only what the family changes is overridden: the
description of the model; the one pool sized from the memory left, in the
program's own bytes a token; the counters and the kernel calls from this
family's counters, span arguments and cost functions
(``perfbench/costs_latent_moe.py``), with each traced call's attention
apart for ``layer_metrics/latent_attend_share_pct.py``.  `_start_pipeline`
(any bundle that carries its description, `prefill_chunk`, `chunk_every`,
the program's own cache stats kept) and `_warm` (the shortest and the
longest prompt, each whole-prompt bucket up to the chunk's, then every row
at once draining to one, so that every decode bucket has run) are the
runners' it subclasses.  ``correct`` is decided as for the dense family.
"""

from __future__ import annotations

import json

from perfbench import costs_latent_moe as costs
from perfbench import harness
from perfbench.costs import DTYPE_BYTES
from perfbench.references import latent_moe_lm
from perfbench.runners import llm, window_moe_llm

#: counters of `extra_stats()["executor"]` this family adds
EXECUTOR_COUNTERS = (
    "kv_tokens_attended", "kv_slots_read", "latents_expanded",
    "chunk_tiles_attended", "expert_pairs_held", "expert_pairs_away",
    "expert_steps_layers", "experts_touched_sum", "expert_load_max_sum",
    "expert_load_chunks", "expert_tile_visits", "expert_tile_rows",
    "chunk_prefills")


def lm_spec(cfg: dict):
    """The program's description of the model, from the configuration
    file's keys.  Fails (HarnessError) on a program that has no such
    family."""
    try:
        from nnstreamer_tpu.llm.spec import LATENT_MOE, LMSpec
    except ImportError as e:
        raise harness.HarnessError(
            f"this program has no latent family (one compressed row a "
            f"token in a pool with no head axis, read absorbed or "
            f"expanded; nnstreamer_tpu.llm.spec): {e}") from e
    m = latent_moe_lm.dims(cfg)
    yarn = {}
    if m["yarn"]:
        yarn = dict(zip(("yarn_factor", "yarn_orig_len", "yarn_beta_fast",
                         "yarn_beta_slow", "yarn_mscale",
                         "yarn_mscale_all_dim"), m["yarn"]))
    grouped = cfg["topk_method"] == "group_limited_greedy"
    return LMSpec(family=LATENT_MOE, n_heads=m["h"], rope_theta=m["theta"],
                  q_rank=m["rq"], kv_rank=m["rkv"], nope_dim=m["nope"],
                  rope_dim=m["rope"], v_dim=m["v"],
                  dense_layers=m["dense"], dense_width=m["f_dense"],
                  shared_width=m["fs"], n_experts=m["e"],
                  experts_per_tok=m["k"], expert_width=m["f"],
                  score_fn=cfg["scoring_func"], route_scale=m["scale"],
                  route_norm=m["renorm"],
                  n_group=m["groups"] if grouped else 0,
                  topk_group=m["topk_group"] if grouped else 0,
                  experts_first=m["first"], experts_held=m["held"],
                  norm_eps=m["eps"], **yarn)


class Runner(window_moe_llm.Runner):
    def __init__(self, cell, seed, seconds, trace, devices):
        llm.Runner.__init__(self, cell, seed, seconds, trace, devices)
        self.spec = lm_spec(self.cfg)       # before any weight is made

    def _num_blocks(self) -> int:
        """What the chip has free beside the weights and the reserve for
        the programs' temporaries, in blocks of the one pool."""
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        s = self.serving
        ms = self.devices[0].memory_stats()
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"])
                - int(s["pool_reserve_bytes"]))
        n = free // (int(s["block_size"]) * costs.kv_bytes_per_token(
            self.cfg, DTYPE_BYTES[self.cfg["dtype"]]))
        if n < 2 * int(s["max_len"]) // int(s["block_size"]):
            raise harness.HarnessError(
                f"only {n} blocks of the latent pool fit beside the weights")
        return int(n)

    def _counters(self) -> dict:
        out = llm.Runner._counters(self)
        ex = self.llm.extra_stats()["executor"]
        out.update({k: ex[k] for k in EXECUTOR_COUNTERS if k in ex})
        return out

    def _readings(self, obs: dict) -> dict:
        t0 = obs["t0"]
        inside = sorted(t for r in obs["reqs"] for t in r.times
                        if t0 <= t < t0 + self.seconds)
        out = {"gen_lag_s": obs["lag"], "counters": obs["snap"],
               "kv_block_bytes": self.cache_stats["block_bytes"],
               "answer_times": [t0] + inside + [t0 + self.seconds],
               "trace_window": obs["tw"], "kernel_calls": {},
               "attend_calls": {}, "host_spans": [], "chunk_spans": []}
        if obs["tw"] is None:
            return out
        decode, chunks, resolved = [], {}, {}
        attend = {"decode_step": [], "prefill": []}

        def on_backend(label, ts, args):
            what = args.get("what")
            if label == "invoke" and what == "llm_decode" \
                    and "kv_tokens" in args:
                rows, kv = int(args["rows"]), int(args["kv_tokens"])
                decode.append(costs.decode_step(
                    self.cfg, rows, kv, int(args.get("experts_touched", 0)),
                    int(args.get("expert_pairs_held", 0))))
                attend["decode_step"].append(
                    costs.decode_attention(self.cfg, rows, kv))
            elif what == "llm_prefill_chunk" and "latents_expanded" in args:
                key = (args.get("req"), int(args["pos0"]))
                if label == "invoke":
                    chunks[key] = args
                # counts that came after the read-back are on the span
                # that resolved the call, under the same names
                if "expert_load_max" in args:
                    resolved[key] = args

        out["host_spans"] = self._host_spans(obs, on_backend)
        m = latent_moe_lm.dims(self.cfg)
        every = (m["layers"] - m["dense"]) * m["held"]
        prefill = []
        for key, args in chunks.items():
            got = resolved.get(key, {})
            clen, pos0 = int(args["clen"]), int(args["pos0"])
            prefill.append(costs.prefill_chunk(
                self.cfg, clen, pos0, int(got.get("experts_touched", every)),
                int(got.get("expert_pairs_held", clen * m["k"]
                            * (m["layers"] - m["dense"])
                            * m["held"] // m["e"]))))
            attend["prefill"].append(
                costs.chunk_attention(self.cfg, clen, pos0))
            if "expert_load_max" in got:
                out["chunk_spans"].append(
                    {"clen": clen, "pos0": pos0,
                     "expert_load_max": int(got["expert_load_max"]),
                     "experts_touched": int(got["experts_touched"])})
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        out["attend_calls"] = attend
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
