"""Runner of kind ``hybrid_llm``: the ``llm`` runner for the hybrid family
(``nnstreamer_tpu/llm/hybrid_lm.py``: layers of linear attention with a
carried state and of block-sparse attention over paged KV).  Only what the
family changes is overridden: the bundle carries the model's own
description (``ModelBundle.lm``) and ``tensor_llm`` is built with
``prefill_chunk`` and ``chunk_every``; the pool is sized beside what the rows hold by slot; the
bytes of a block and of a slot are read from the program
(``extra_stats()["cache"]``), not reckoned here; the counters and the kernel
calls come from this family's counters, span arguments and cost functions
(``perfbench/costs_hybrid_lm.py``).  ``correct`` is decided as for the dense
family.

A fourth family copies this file's shape: a ``references/<family>.py``
(``dims``, ``make_params``, ``forward_logits``, ``served_token_gaps``), a
``costs_<family>.py``, and a runner that subclasses ``llm.Runner`` with
``lm_spec`` (which fails as ``HarnessError`` on a program without the
family, so that a parent commit exits at once), `_num_blocks`,
`_start_pipeline` (the sparse-expert runner's serves any bundle that
carries its description; this one's adds a serving option), `_warm` where its prompts need it, `_counters`
and `_readings`.
"""

from __future__ import annotations

import json
import time

from perfbench import costs_hybrid_lm as costs
from perfbench import harness, traffic
from perfbench.costs import DTYPE_BYTES
from perfbench.references import hybrid_lm
from perfbench.runners import llm

#: counters of `extra_stats()["executor"]` this family adds
EXECUTOR_COUNTERS = (
    "state_bytes_rw", "ckeys_scored", "kv_blocks_selected",
    "kv_tokens_selected", "kv_slots_read", "chunk_prefills")


def lm_spec(cfg: dict):
    """The program's description of the model, from the configuration
    file's keys.  Fails (HarnessError) on a program that has no such
    family."""
    try:
        from nnstreamer_tpu.llm.spec import HYBRID, LMSpec
    except ImportError as e:
        raise harness.HarnessError(
            f"this program has no hybrid family (linear attention with a "
            f"carried state beside block-sparse attention; "
            f"nnstreamer_tpu.llm.spec): {e}") from e
    m = hybrid_lm.dims(cfg)
    if m["lhd"] != m["hd"]:
        raise harness.HarnessError(
            "the program's description has one head size for both kinds "
            "of layer")
    return LMSpec(family=HYBRID, n_heads=m["h"], n_kv=m["hkv"],
                  head_dim=m["hd"], rope_theta=m["theta"], qk_norm=True,
                  layer_kinds=m["kinds"], lin_heads=m["lh"],
                  ck_kernel=m["kernel"], ck_stride=m["stride"],
                  sel_block=m["block"], sel_topk=m["topk"],
                  sel_window=m["window"], sel_init=m["init"],
                  emb_scale=m["emb_scale"], residual_scale=m["r"],
                  logit_div=m["logit_div"])


class Runner(llm.Runner):
    def __init__(self, cell, seed, seconds, trace, devices):
        super().__init__(cell, seed, seconds, trace, devices)
        self.spec = lm_spec(self.cfg)       # before any weight is made

    def _num_blocks(self) -> int:
        """What the chip has free beside the weights, what the rows
        hold by slot (a state and the compressed keys of the longest
        sequence each, and the scratch slot's) and the reserve for the
        programs' temporaries, in blocks."""
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        s = self.serving
        ms = self.devices[0].memory_stats()
        wb = DTYPE_BYTES[self.cfg["dtype"]]
        state = (int(s["max_batch"]) + 1) * (
            costs.state_bytes_per_seq(self.cfg)
            + costs.ckey_bytes_per_seq(self.cfg, s["max_len"], wb))
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"]) - state
                - int(s["pool_reserve_bytes"]))
        block = int(s["block_size"]) * costs.kv_bytes_per_token(
            self.cfg, wb)
        n = free // block
        if n < 2 * int(s["max_len"]) // int(s["block_size"]):
            raise harness.HarnessError(
                f"only {n} pool blocks fit beside the weights and the "
                f"state pool")
        return int(n)

    def _start_pipeline(self) -> None:
        """The sparse-expert runner's, with the one option more that
        this configuration's serving sets (`chunk_every`)."""
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
            chunk_every=int(s["chunk_every"]),
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
        # what the program itself says a block and a slot hold
        self.cache_stats = dict(self.llm.extra_stats()["cache"])

    def _warm(self) -> None:
        """The shortest and the longest prompt of the mix once (all
        lengths go through the one chunk program, whose loops' trip
        counts are values), then one batch that fills every row at once
        and drains to one, so that every decode bucket has run.  The
        prompts of that batch are one chunk and one block long: rows
        that took a mix prompt's many chunks to start would never be
        live together."""
        rng = traffic.rng_for(self.seed, "warm")
        lengths = sorted({int(p) for p, _ in self.cell.traffic["items"]})
        self.warm_detail = {}
        for i, n in enumerate(sorted({lengths[0], lengths[-1]})):
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
                             2 * rows + 2 * i) for i in range(rows)]
        for r in batch:
            self._submit(r)
        self._wait_done(batch, 900.0)
        self.warm_detail["decode_rows_down"] = round(
            time.perf_counter() - t, 3)

    def _counters(self) -> dict:
        out = super()._counters()
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
               "host_spans": [], "decode_state_bytes": []}
        if obs["tw"] is None:
            return out
        decode, prefill = [], []
        state = costs.state_bytes_per_seq(self.cfg)

        def on_backend(label, ts, args):
            if label != "invoke" or "ckeys_scored" not in args:
                return
            scored = int(args["ckeys_scored"])
            selected = int(args["kv_selected"])
            if args.get("what") == "llm_decode":
                decode.append(costs.decode_step(
                    self.cfg, int(args["rows"]), scored, selected))
                out["decode_state_bytes"].append(
                    2 * int(args["state_rows"]) * state)
            elif args.get("what") == "llm_prefill_chunk":
                prefill.append(costs.prefill_chunk(
                    self.cfg, int(args["clen"]), int(args["pos0"]), scored,
                    selected))

        out["host_spans"] = self._host_spans(obs, on_backend)
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
