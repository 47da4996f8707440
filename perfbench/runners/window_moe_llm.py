"""Runner of kind ``window_moe_llm``: the ``hybrid_llm`` runner for the
window family (``nnstreamer_tpu/llm/window_moe.py``: layers that attend a
window or the whole context over a pool a kind, a shared expert beside
this chip's share of the routed experts).  Only what the family changes is
overridden: the description of the model; the full layers' pool sized
beside the window layers' (which the program sizes itself, by its rows);
``chunk_every``; the bytes of a block of each pool read from the program
(``extra_stats()["cache"]``), so that ``kv_live_gb`` counts both pools with
the program's own bytes; the counters and the kernel calls from this
family's counters, span arguments and cost functions
(``perfbench/costs_window_moe.py``).  ``correct`` is decided as for the
dense family.  It subclasses ``hybrid_llm.Runner`` for the one method it
keeps whole, `_start_pipeline` (any bundle that carries its description,
`prefill_chunk`, `chunk_every`, and the program's own cache stats kept);
of ``sparse_moe_llm``'s overrides none would be left standing.
"""

from __future__ import annotations

import json
import time

from perfbench import costs_window_moe as costs
from perfbench import harness, traffic
from perfbench.costs import DTYPE_BYTES
from perfbench.references import window_moe_lm
from perfbench.runners import hybrid_llm, llm

#: counters of `extra_stats()["executor"]` this family adds
EXECUTOR_COUNTERS = (
    "kv_tokens_full", "kv_tokens_window", "kv_slots_read",
    "expert_pairs_held", "expert_pairs_away", "expert_steps_layers",
    "experts_touched_sum", "expert_load_max_sum", "expert_load_chunks",
    "ctx_tiles_full", "ctx_tiles_window", "chunk_prefills")


def lm_spec(cfg: dict):
    """The program's description of the model, from the configuration
    file's keys.  Fails (HarnessError) on a program that has no such
    family."""
    try:
        from nnstreamer_tpu.llm.spec import FULL, WINDOW, WINDOW_MOE, LMSpec
    except ImportError as e:
        raise harness.HarnessError(
            f"this program has no window family (layers of window and of "
            f"full attention over a pool a kind, a share of the routed "
            f"experts; nnstreamer_tpu.llm.spec): {e}") from e
    m = window_moe_lm.dims(cfg)
    kinds = tuple(WINDOW if k == window_moe_lm.SLIDING else FULL
                  for k in m["kinds"])
    return LMSpec(family=WINDOW_MOE, n_heads=m["h"], n_kv=m["hkv"],
                  head_dim=m["hd"], rope_theta=m["theta"],
                  layer_kinds=kinds, window=m["window"],
                  dense_layers=m["dense"],
                  dense_width=m["f_dense"], shared_width=m["fs"],
                  n_experts=m["e"], experts_per_tok=m["k"],
                  expert_width=m["f"], score_fn=cfg["score_func"],
                  route_scale=m["scale"], experts_first=m["first"],
                  experts_held=m["held"], emb_scale=m["emb_scale"],
                  norm_eps=m["eps"])


class Runner(hybrid_llm.Runner):
    def __init__(self, cell, seed, seconds, trace, devices):
        llm.Runner.__init__(self, cell, seed, seconds, trace, devices)
        self.spec = lm_spec(self.cfg)       # before any weight is made

    def _num_blocks(self) -> int:
        """What the chip has free beside the weights, the window layers'
        pools (as the program will size them: every row at its decode
        cap, one chunk's more, the scratch block) and the reserve for
        the programs' temporaries, in blocks of the full layers'
        pools."""
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        s = self.serving
        ms = self.devices[0].memory_stats()
        wb = DTYPE_BYTES[self.cfg["dtype"]]
        bs = int(s["block_size"])
        window = costs.window_pool_blocks(
            self.cfg, int(s["max_batch"]), int(s["prefill_chunk"]), bs) \
            * bs * costs.kv_bytes_per_token(self.cfg, wb, "window")
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"]) - window
                - int(s["pool_reserve_bytes"]))
        n = free // (bs * costs.kv_bytes_per_token(self.cfg, wb, "full"))
        if n < 2 * int(s["max_len"]) // bs:
            raise harness.HarnessError(
                f"only {n} blocks of the full layers' pool fit beside the "
                f"weights and the window layers' pools")
        return int(n)

    def _warm(self) -> None:
        """The shortest and the longest prompt of the mix once (all
        lengths past one chunk go through the one chunk program, whose
        loops' bounds are values), each whole-prompt bucket below the
        chunk's, then one batch that fills every row at once and drains
        to one, so that every decode bucket has run."""
        rng = traffic.rng_for(self.seed, "warm")
        chunk = int(self.serving["prefill_chunk"])
        lengths = sorted({int(p) for p, _ in self.cell.traffic["items"]})
        once = sorted({lengths[0], lengths[-1]}
                      | {n for n in lengths if n <= chunk})
        self.warm_detail = {}
        for i, n in enumerate(once):
            t = time.perf_counter()
            r = llm.Request(f"w{i}", self._prompt(rng, n), 1)
            self._submit(r)
            self._wait_done([r], 900.0)
            self.warm_detail[f"prompt_{n}"] = round(
                time.perf_counter() - t, 3)
        t = time.perf_counter()
        rows = int(self.serving["max_batch"])
        batch = [llm.Request(f"wb{i}", self._prompt(rng, lengths[0]),
                             2 * rows + 2 * i) for i in range(rows)]
        for r in batch:
            self._submit(r)
        self._wait_done(batch, 900.0)
        self.warm_detail["decode_rows_down"] = round(
            time.perf_counter() - t, 3)

    def _counters(self) -> dict:
        out = llm.Runner._counters(self)
        st = self.llm.extra_stats()
        ex, cache = st["executor"], st["cache"]
        out.update({k: ex[k] for k in EXECUTOR_COUNTERS if k in ex})
        win = cache["window"]
        out["admission_blocked_window"] = st["admission_blocked_window"]
        out["window_blocks_used"] = win["blocks_used"]
        out["window_blocks_freed"] = cache["window_blocks_freed"]
        # both pools' live bytes in blocks of the full layers' pool, so
        # that `kv_live_gb` (blocks x `kv_block_bytes`) counts both
        out["kv_blocks_used"] = cache["blocks_used"] + (
            win["blocks_used"] * win["block_bytes"] / cache["block_bytes"])
        return out

    def _readings(self, obs: dict) -> dict:
        t0 = obs["t0"]
        inside = sorted(t for r in obs["reqs"] for t in r.times
                        if t0 <= t < t0 + self.seconds)
        out = {"gen_lag_s": obs["lag"], "counters": obs["snap"],
               "kv_block_bytes": self.cache_stats["block_bytes"],
               "window_block_bytes":
                   self.cache_stats["window"]["block_bytes"],
               "answer_times": [t0] + inside + [t0 + self.seconds],
               "trace_window": obs["tw"], "kernel_calls": {},
               "host_spans": [], "chunk_spans": []}
        if obs["tw"] is None:
            return out
        decode, chunks, resolved = [], {}, {}

        def on_backend(label, ts, args):
            what = args.get("what")
            if label == "invoke" and what == "llm_decode" \
                    and "kv_tokens_window" in args:
                decode.append(costs.decode_step(
                    self.cfg, int(args["rows"]), int(args["kv_tokens_full"]),
                    int(args["kv_tokens_window"]),
                    int(args.get("experts_touched", 0)),
                    int(args.get("expert_pairs_held", 0))))
            elif what == "llm_prefill_chunk" and "ctx_tiles_window" in args:
                key = (args.get("req"), int(args["pos0"]))
                if label == "invoke":
                    chunks[key] = args
                # counts that came after the read-back are on the span
                # that resolved the call, under the same names
                if "expert_load_max" in args:
                    resolved[key] = args

        out["host_spans"] = self._host_spans(obs, on_backend)
        m = window_moe_lm.dims(self.cfg)
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
            if "expert_load_max" in got:
                out["chunk_spans"].append(
                    {"clen": clen, "pos0": pos0,
                     "expert_load_max": int(got["expert_load_max"]),
                     "experts_touched": int(got["experts_touched"])})
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
