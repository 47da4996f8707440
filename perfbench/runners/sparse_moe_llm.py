"""Runner of kind ``sparse_moe_llm``: the ``llm`` runner for the
sparse-expert family (``nnstreamer_tpu/llm/sparse_moe.py``).  Only what
the family changes is overridden: the bundle carries the model's own
description (``ModelBundle.lm``) and ``tensor_llm`` is built with
``prefill_chunk``; a block of the pool also holds the indexer's keys; the
counters and the kernel calls come from this family's counters, span
arguments and cost functions (``perfbench/costs_sparse_moe.py``).
``correct`` is decided as for the dense family.

A third family would do the same: a ``references/<family>.py``, a
``costs_<family>.py``, and a runner that subclasses ``llm.Runner`` and
overrides `_start_pipeline`, `_num_blocks`, `_warm` where its prompts
need it, `_counters` and `_readings`.
"""

from __future__ import annotations

import json
import time

from perfbench import costs_sparse_moe as costs
from perfbench import harness, traffic
from perfbench.references import sparse_moe_lm
from perfbench.runners import llm

#: counters of `extra_stats()["executor"]` this family adds
EXECUTOR_COUNTERS = (
    "kv_tokens_scored", "kv_tokens_selected", "idx_slots_read",
    "kv_slots_read", "expert_tokens", "expert_steps_layers",
    "experts_touched_sum", "expert_load_max_sum", "expert_load_chunks",
    "chunk_prefills")


def lm_spec(cfg: dict):
    """The program's description of the model, from the configuration
    file's published keys.  Fails (HarnessError) on a program that has
    no such family."""
    try:
        from nnstreamer_tpu.llm.spec import SPARSE_MOE, LMSpec
    except ImportError as e:
        raise harness.HarnessError(
            f"this program has no sparse-expert family "
            f"(nnstreamer_tpu.llm.spec): {e}") from e
    m = sparse_moe_lm.dims(cfg)
    return LMSpec(family=SPARSE_MOE, n_heads=m["h"], n_kv=m["hkv"],
                  head_dim=m["hd"], rope_theta=m["theta"], qk_norm=True,
                  idx_heads=m["hi"], idx_dim=m["di"], topk=m["topk"],
                  n_experts=m["e"], experts_per_tok=m["k"],
                  expert_width=m["f"])


class Runner(llm.Runner):
    def __init__(self, cell, seed, seconds, trace, devices):
        super().__init__(cell, seed, seconds, trace, devices)
        self.spec = lm_spec(self.cfg)       # before any weight is made

    def _block_bytes(self) -> int:
        return (costs.kv_bytes_per_token(self.cfg, llm.POOL_DTYPE_BYTES)
                * int(self.serving["block_size"]))

    def _num_blocks(self) -> int:
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        ms = self.devices[0].memory_stats()
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"])
                - int(self.serving["pool_reserve_bytes"]))
        n = free // self._block_bytes()
        if n < 2 * int(self.serving["max_len"]) // int(
                self.serving["block_size"]):
            raise harness.HarnessError(
                f"only {n} pool blocks fit beside the weights")
        return int(n)

    def _start_pipeline(self) -> None:
        import nnstreamer_tpu as nns
        from nnstreamer_tpu.backends.xla import ModelBundle
        from nnstreamer_tpu.elements import AppSrc, TensorLLM, TensorSink
        from nnstreamer_tpu.serving.store import get_store
        from nnstreamer_tpu.tensor.info import TensorFormat, TensorsSpec

        get_store().register(self.model_name, ModelBundle(
            fn=None, params=self.params, lm=self.spec))
        s = self.serving
        self.num_blocks = self._num_blocks()
        self.src = AppSrc(name="src", spec=TensorsSpec(
            tensors=(), format=TensorFormat.FLEXIBLE))
        self.llm = TensorLLM(
            name="llm", model=f"store://{self.model_name}",
            dtype=self.cfg["dtype"], max_batch=int(s["max_batch"]),
            num_blocks=self.num_blocks, block_size=int(s["block_size"]),
            max_len=int(s["max_len"]), eos_id=int(s["eos_id"]),
            paged_kernel=s["paged_kernel"],
            prefill_chunk=int(s["prefill_chunk"]),
            admit_window_ms=float(s["admit_window_ms"]),
            # the benchmark warms this cell's shapes itself
            warm_start=0, prewarm=0)
        sink = TensorSink(name="sink", new_data=self._on_chunk,
                          collect=False)
        self.pipe = nns.Pipeline()
        for e in (self.src, self.llm, sink):
            self.pipe.add(e)
        self.pipe.link(self.src, self.llm)
        self.pipe.link(self.llm, sink)
        self.runner = nns.PipelineRunner(self.pipe, trace=self._tracer())
        self.runner.start()

    def _warm(self) -> None:
        """Every prompt length of the mix once (all through the one chunk
        program), then one batch that fills every row at once and drains
        to one, so that every decode bucket has run.  The prompts of
        that batch are one chunk and one block long: rows that took a
        mix prompt's many chunks to start would never be live together."""
        rng = traffic.rng_for(self.seed, "warm")
        lengths = sorted({int(p) for p, _ in self.cell.traffic["items"]})
        self.warm_detail = {}
        for i, n in enumerate(lengths):
            t = time.perf_counter()
            r = llm.Request(f"w{i}", self._prompt(rng, n), 1)
            self._submit(r)
            self._wait_done([r], 900.0)
            self.warm_detail[f"prompt_{n}"] = round(
                time.perf_counter() - t, 3)
        t = time.perf_counter()
        rows = int(self.serving["max_batch"])
        plen = min(int(self.serving["prefill_chunk"])
                   + int(self.serving["block_size"]), lengths[0])
        batch = [llm.Request(f"wb{i}", self._prompt(rng, plen),
                             4 * rows + 2 * i) for i in range(rows)]
        for r in batch:
            self._submit(r)
        self._wait_done(batch, 900.0)
        self.warm_detail["decode_rows_down"] = round(
            time.perf_counter() - t, 3)

    def _counters(self) -> dict:
        out = super()._counters()
        ex = self.llm.extra_stats()["executor"]
        out.update({k: ex[k] for k in EXECUTOR_COUNTERS if k in ex})
        return out

    def _readings(self, obs: dict) -> dict:
        t0 = obs["t0"]
        inside = sorted(t for r in obs["reqs"] for t in r.times
                        if t0 <= t < t0 + self.seconds)
        out = {"gen_lag_s": obs["lag"], "counters": obs["snap"],
               "kv_block_bytes": self._block_bytes(),
               "answer_times": [t0] + inside + [t0 + self.seconds],
               "trace_window": obs["tw"], "kernel_calls": {},
               "host_spans": [], "chunk_spans": []}
        if obs["tw"] is None:
            return out
        decode, chunks, resolved = [], {}, {}

        def on_backend(label, ts, args):
            what = args.get("what")
            if label == "invoke" and what == "llm_decode" \
                    and "kv_selected" in args:
                decode.append(costs.decode_step(
                    self.cfg, int(args["rows"]), int(args["kv_tokens"]),
                    int(args["kv_selected"]),
                    int(args.get("experts_touched", 0))))
            elif what == "llm_prefill_chunk" and "pos0" in args:
                key = (args.get("req"), int(args["pos0"]))
                if label == "invoke":
                    chunks[key] = args
                # counts that came after the read-back are on the span
                # that resolved the call, under the same names
                if "expert_load_max" in args:
                    resolved[key] = args

        out["host_spans"] = self._host_spans(obs, on_backend)
        layers = int(self.cfg["num_hidden_layers"])
        every = layers * int(self.cfg["num_experts"])
        prefill = []
        for key, args in chunks.items():
            got = resolved.get(key, {})
            prefill.append(costs.prefill_chunk(
                self.cfg, int(args["clen"]), int(args["pos0"]),
                int(got.get("experts_touched", every))))
            if "expert_load_max" in got:
                out["chunk_spans"].append(
                    {"clen": int(args["clen"]), "pos0": int(args["pos0"]),
                     "expert_load_max": int(got["expert_load_max"]),
                     "experts_touched": int(got["experts_touched"])})
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
