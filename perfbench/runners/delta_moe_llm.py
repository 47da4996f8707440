"""Runner of kind ``delta_moe_llm``: the ``latent_moe_llm`` runner for the
delta family (``nnstreamer_tpu/llm/delta_moe.py``: layers of gated
delta-rule linear attention, a float32 state and three convolutions' tails
a sequence by slot, beside latent layers without rope over a pool by block;
a shared expert beside this chip's share of the routed experts).  Only what
the family changes is overridden: the description of the model; the latent
pool sized beside what the rows hold by slot, both in the program's own
bytes; the counters and the kernel calls from this family's counters, span
arguments and cost functions (``perfbench/costs_delta_moe.py``), with each
traced call's latent attention and its delta-rule part apart for
``layer_metrics/latent_attend_share_pct.py`` and ``delta_share_pct.py``,
and what ``state_live_gb`` and ``state_share_of_step_pct`` read.
`_start_pipeline` and `_warm` are the runners' it subclasses.  ``correct``
is decided as for the dense family.
"""

from __future__ import annotations

import json

from perfbench import costs_delta_moe as costs
from perfbench import harness
from perfbench.costs import DTYPE_BYTES
from perfbench.references import delta_moe_lm
from perfbench.runners import latent_moe_llm, llm

#: counters of `extra_stats()["executor"]` this family adds
EXECUTOR_COUNTERS = latent_moe_llm.EXECUTOR_COUNTERS + (
    "state_rows", "state_bytes_rw", "tail_bytes_rw", "chunks_fresh",
    "delta_runs", "decode_steps_fused", "decode_steps_plain")


def lm_spec(cfg: dict):
    """The program's description of the model, from the configuration
    file's keys.  Fails (HarnessError) on a program that has no such
    family."""
    try:
        from nnstreamer_tpu.llm.spec import DELTA_MOE, KDA, LATENT, LMSpec
    except ImportError as e:
        raise harness.HarnessError(
            f"this program has no delta family (gated delta-rule layers "
            f"with a state and convolution tails a sequence beside latent "
            f"layers over a paged pool; nnstreamer_tpu.llm.spec): {e}") from e
    m = delta_moe_lm.dims(cfg)
    if cfg.get("q_lora_rank") or not cfg["mla_use_nope"]:
        raise harness.HarnessError(
            "this runner describes a latent query with no rank and no rope")
    kinds = tuple(KDA if k == delta_moe_lm.KDA else LATENT
                  for k in m["kinds"])
    return LMSpec(family=DELTA_MOE, n_heads=m["h"], head_dim=m["kd"],
                  lin_heads=m["kh"], conv_kernel=m["conv"],
                  layer_kinds=kinds, q_rank=0,
                  roped=False, kv_rank=m["rkv"], nope_dim=m["nope"],
                  rope_dim=m["rope"], v_dim=m["v"],
                  dense_layers=m["dense"], dense_width=m["f_dense"],
                  shared_width=m["fs"], n_experts=m["e"],
                  experts_per_tok=m["k"], expert_width=m["f"],
                  score_fn=cfg["moe_router_activation_func"],
                  route_scale=m["scale"], experts_first=m["first"],
                  experts_held=m["held"], norm_eps=m["eps"])


class Runner(latent_moe_llm.Runner):
    def __init__(self, cell, seed, seconds, trace, devices):
        llm.Runner.__init__(self, cell, seed, seconds, trace, devices)
        self.spec = lm_spec(self.cfg)       # before any weight is made

    def _num_blocks(self) -> int:
        """What the chip has free beside the weights, what the rows hold
        by slot (a state and the tails each, and the scratch slot's) and
        the reserve for the programs' temporaries, in blocks of the
        latent layers' pool."""
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        s = self.serving
        ms = self.devices[0].memory_stats()
        slots = (int(s["max_batch"]) + 1) * costs.slot_bytes_per_seq(self.cfg)
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"]) - slots
                - int(s["pool_reserve_bytes"]))
        n = free // (int(s["block_size"]) * costs.kv_bytes_per_token(
            self.cfg, DTYPE_BYTES[self.cfg["dtype"]]))
        if n < 2 * int(s["max_len"]) // int(s["block_size"]):
            raise harness.HarnessError(
                f"only {n} blocks of the latent pool fit beside the weights "
                f"and the rows' slots")
        return int(n)

    def _counters(self) -> dict:
        out = llm.Runner._counters(self)
        st = self.llm.extra_stats()
        ex = st["executor"]
        out.update({k: ex[k] for k in EXECUTOR_COUNTERS if k in ex})
        out["admission_blocked_state"] = st["admission_blocked_state"]
        out["state_slots_used"] = st["cache"]["state_slots_used"]
        return out

    def _readings(self, obs: dict) -> dict:
        t0 = obs["t0"]
        inside = sorted(t for r in obs["reqs"] for t in r.times
                        if t0 <= t < t0 + self.seconds)
        out = {"gen_lag_s": obs["lag"], "counters": obs["snap"],
               "kv_block_bytes": self.cache_stats["block_bytes"],
               "state_slot_bytes": self.cache_stats["state_slot_bytes"],
               "answer_times": [t0] + inside + [t0 + self.seconds],
               "trace_window": obs["tw"], "kernel_calls": {},
               "attend_calls": {}, "delta_calls": {}, "host_spans": [],
               "chunk_spans": [], "decode_state_bytes": []}
        if obs["tw"] is None:
            return out
        decode, chunks, resolved = [], {}, {}
        attend = {"decode_step": [], "prefill": []}
        delta = {"decode_step": [], "prefill": []}

        def on_backend(label, ts, args):
            what = args.get("what")
            if label == "invoke" and what == "llm_decode" \
                    and "state_rows" in args:
                rows, kv = int(args["rows"]), int(args["kv_tokens"])
                decode.append(costs.decode_step(
                    self.cfg, rows, kv, int(args.get("experts_touched", 0)),
                    int(args.get("expert_pairs_held", 0))))
                attend["decode_step"].append(
                    costs.decode_attention(self.cfg, rows, kv))
                delta["decode_step"].append(
                    costs.decode_delta(self.cfg, rows))
                out["decode_state_bytes"].append(int(args["state_bytes_rw"]))
            elif what == "llm_prefill_chunk" and "delta_runs" in args:
                key = (args.get("req"), int(args["pos0"]))
                if label == "invoke":
                    chunks[key] = args
                # counts that came after the read-back are on the span
                # that resolved the call, under the same names
                if "expert_load_max" in args:
                    resolved[key] = args

        out["host_spans"] = self._host_spans(obs, on_backend)
        m = delta_moe_lm.dims(self.cfg)
        layers = m["layers"] - m["dense"]
        prefill = []
        for key, args in chunks.items():
            got = resolved.get(key, {})
            clen, pos0 = int(args["clen"]), int(args["pos0"])
            prefill.append(costs.prefill_chunk(
                self.cfg, clen, pos0,
                int(got.get("experts_touched", layers * m["held"])),
                int(got.get("expert_pairs_held", clen * m["k"] * layers
                            * m["held"] // m["e"]))))
            attend["prefill"].append(
                costs.chunk_attention(self.cfg, clen, pos0))
            delta["prefill"].append(
                costs.chunk_delta(self.cfg, clen, pos0 == 0))
            if "expert_load_max" in got:
                out["chunk_spans"].append(
                    {"clen": clen, "pos0": pos0,
                     "expert_load_max": int(got["expert_load_max"]),
                     "experts_touched": int(got["experts_touched"])})
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        out["attend_calls"], out["delta_calls"] = attend, delta
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
