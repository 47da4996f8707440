"""Runner of kind ``llm``: ``appsrc ! tensor_llm ! tensor_sink`` on a
store-registered bundle whose weights the benchmark made from the seed.

From the program it takes the elements, the model store, and the counters
and spans they expose.  Requests, clocks, metrics and the reference are the
benchmark's own.
"""

from __future__ import annotations

import bisect
import gc
import json
import time
from typing import Dict, List

import numpy as np

from perfbench import costs, harness, traffic
from perfbench.harness import Check, Outcome, at_most
from perfbench.runners._base import RunnerBase

POOL_DTYPE_BYTES = 4        # the program keeps its KV pool in float32


class Request:
    __slots__ = ("rid", "prompt", "out_len", "times", "tokens", "done")

    def __init__(self, rid, prompt, out_len):
        self.rid, self.prompt, self.out_len = rid, prompt, int(out_len)
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.done = False

    def started_by(self, t: float) -> bool:
        return bool(self.times) and self.times[0] < t

    def finished_by(self, t: float) -> bool:
        return self.done and self.times[-1] < t


class Runner(RunnerBase):
    def __init__(self, cell: harness.Cell, seed: int, seconds: float,
                 trace: bool, devices):
        super().__init__(cell, seed, seconds, trace, devices)
        self.requests: Dict[str, Request] = {}
        self.last_token = 0.0
        self.pipe = self.src = self.llm = None

    # -- set-up ----------------------------------------------------------------
    def setup(self, phases: harness.Phases) -> None:
        import jax

        self.params = self.ref.make_params(self.cfg, self.seed)
        jax.block_until_ready(self.params)
        phases.mark("weights")
        self._start_pipeline()
        phases.mark("start")
        self._warm()
        phases.mark("warm")

    def _num_blocks(self) -> int:
        if "num_blocks" in self.serving:
            return int(self.serving["num_blocks"])
        ms = self.devices[0].memory_stats()
        free = (int(ms["bytes_limit"]) - int(ms["bytes_in_use"])
                - int(self.serving["pool_reserve_bytes"]))
        block = (costs.lm_kv_bytes_per_token(self.cfg, POOL_DTYPE_BYTES)
                 * int(self.serving["block_size"]))
        n = free // block
        if n < 2 * int(self.serving["max_len"]) // int(
                self.serving["block_size"]):
            raise harness.HarnessError(
                f"only {n} KV blocks fit beside the weights")
        return int(n)

    def _start_pipeline(self) -> None:
        import nnstreamer_tpu as nns
        from nnstreamer_tpu.backends.xla import ModelBundle
        from nnstreamer_tpu.elements import AppSrc, TensorLLM, TensorSink
        from nnstreamer_tpu.serving.store import get_store
        from nnstreamer_tpu.tensor.info import TensorFormat, TensorsSpec

        get_store().register(self.model_name,
                             ModelBundle(fn=None, params=self.params))
        s = self.serving
        self.num_blocks = self._num_blocks()
        self.src = AppSrc(name="src", spec=TensorsSpec(
            tensors=(), format=TensorFormat.FLEXIBLE))
        self.llm = TensorLLM(
            name="llm", model=f"store://{self.model_name}",
            n_heads=int(self.cfg["num_attention_heads"]),
            dtype=self.cfg["dtype"], max_batch=int(s["max_batch"]),
            num_blocks=self.num_blocks, block_size=int(s["block_size"]),
            max_len=int(s["max_len"]), eos_id=int(s["eos_id"]),
            paged_kernel=s["paged_kernel"],
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

    def _on_chunk(self, buf) -> None:
        now = self.last_token = time.perf_counter()
        m = buf.meta["llm"]
        req = self.requests[m["request_id"]]
        toks = np.asarray(buf.tensors[0]).reshape(-1)
        req.tokens.extend(int(t) for t in toks)
        req.times.extend([now] * len(toks))
        if m["done"]:
            req.done = True

    def _submit(self, req: Request) -> None:
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        self.requests[req.rid] = req
        self.src.push(TensorBuffer(
            tensors=(req.prompt,), pts=len(self.requests),
            meta={"llm": {"request_id": req.rid,
                          "max_new_tokens": req.out_len}}))

    def _prompt(self, rng, length: int) -> np.ndarray:
        return rng.integers(0, int(self.cfg["vocab_size"]), int(length),
                            dtype=np.int64).astype(np.int32)

    def _wait_done(self, reqs: List[Request], timeout: float) -> None:
        deadline = time.perf_counter() + timeout
        while not all(r.done for r in reqs):
            self._raise_if_failed()
            if time.perf_counter() > deadline:
                raise harness.HarnessError(
                    f"{sum(not r.done for r in reqs)} of {len(reqs)} "
                    f"requests unanswered after {timeout:.0f} s")
            time.sleep(0.01)

    def _warm(self) -> None:
        """Drive every shape the cell's traffic uses through the served
        path once: each prompt length of the mix (one token out, so
        prefill only), then one batch that drains from `max_batch` rows
        to one, so that every decode batch size has run."""
        rng = traffic.rng_for(self.seed, "warm")
        lengths = sorted({int(p) for p, _ in self.cell.traffic["items"]})
        first = [Request(f"w{i}", self._prompt(rng, n), 1)
                 for i, n in enumerate(lengths)]
        self.warm_detail = {}
        for r in first:
            t = time.perf_counter()
            self._submit(r)
            self._wait_done([r], 900.0)
            self.warm_detail[f"prompt_{len(r.prompt)}"] = round(
                time.perf_counter() - t, 3)
        t = time.perf_counter()
        rows = int(self.serving["max_batch"])
        batch = [Request(f"wb{i}", self._prompt(rng, lengths[0]), 8 + 2 * i)
                 for i in range(rows)]
        for r in batch:
            self._submit(r)
        self._wait_done(batch, 900.0)
        self.warm_detail["decode_rows_down"] = round(
            time.perf_counter() - t, 3)

    # -- the window ------------------------------------------------------------
    def _counters(self) -> dict:
        st = self.llm.extra_stats()
        ex = st["executor"]
        return {"t": time.perf_counter(),
                "compile_count": ex["compile_count"],
                "decode_steps": ex["decode_steps"],
                "decode_tokens": st["tokens_out"] - ex["prefills"],
                "steps": st["steps"],
                "admission_blocked": st["admission_blocked"],
                "kv_blocks_used": st["cache"]["blocks_used"]}

    def window(self, phases: harness.Phases) -> dict:
        """Queue the whole backlog `ramp_s` ahead, then count inside
        [t0, t0 + seconds)."""
        arrivals, t0, tw = self._open_window(phases)
        items = self.cell.traffic["items"]
        rng = traffic.rng_for(self.seed, "tokens")
        reqs = [Request(f"r{i}", self._prompt(rng, items[a.item][0]),
                        items[a.item][1]) for i, a in enumerate(arrivals)]
        lag = harness.drive(arrivals, t0,
                            lambda a, it=iter(reqs): self._submit(next(it)),
                            until=t0 + self.seconds)
        snap = {}
        harness.sleep_until(t0)
        snap["start"] = self._counters()
        with harness.StallWatch(lambda: self.last_token):
            harness.sleep_until(t0 + self.seconds)
        snap["end"] = self._counters()
        self._raise_if_failed()
        if tw is not None:
            tw.join()
        return {"t0": t0, "lag": lag, "snap": snap, "tw": tw,
                "reqs": [r for r in reqs if r.rid in self.requests],
                "tracer_events": self._tracer_events()}

    def teardown(self) -> None:
        """Stop the pipeline and free the program's device state, so
        that the reference runs beside the weights alone."""
        if self.runner is not None:
            harness.stop_and_join(self)
        from nnstreamer_tpu.serving.store import reset_store

        if self.llm is not None:
            self.llm.engine = None
        self.runner = self.pipe = self.llm = self.src = None
        reset_store()       # the store's hold on this seed's weights
        gc.collect()

    # -- metrics -----------------------------------------------------------------
    def outcome(self, obs: dict) -> Outcome:
        t0, t1 = obs["t0"], obs["t0"] + self.seconds
        reqs: List[Request] = obs["reqs"]       # in the order submitted
        j = self._judge(reqs, t1)
        in_window = sum(1 for r in reqs for t in r.times if t0 <= t < t1)
        harness.log(harness.window_rate_line(
            [t for r in reqs for t in r.times], t0, self.seconds))
        harness.log("requests " + json.dumps(
            {k: len(v) for k, v in j.items()}))
        snap = obs["snap"]
        compiles = (snap["end"]["compile_count"]
                    - snap["start"]["compile_count"])
        checks = [
            at_most("requests_passed_over", len(j["passed_over"]), 0),
            at_most("requests_in_flight", len(j["in_flight"]),
                    int(self.serving["max_batch"])),
            at_most("answers_of_wrong_length", len(j["wrong"]), 0),
            Check("pipeline_error", repr(self.pipeline_error), "None",
                  self.pipeline_error is None),
            at_most("compiles_in_window", compiles, 0),
        ]
        checks.extend(self._served_token_checks(j["good"]))
        return Outcome(len(j["judged"]),
                       len(j["wrong"]) + len(j["passed_over"]),
                       {"tokens_per_s": in_window / self.seconds}, checks,
                       self._readings(obs))

    @staticmethod
    def _judge(reqs: List[Request], t1: float) -> Dict[str, List[Request]]:
        """The backlog by the order it was submitted in.  `judged` is
        every request up to the last that finished inside the window:
        each of them has to have finished with its stated length or to be
        in flight, so a request that later ones overtook before it got
        its first token (`passed_over`) is a failure, and speed cannot
        come from requests left lying.  `in_flight` counts every request
        with tokens and no end when the window closed: the engine's rows
        hold no more than `max_batch`."""
        last = max((i for i, r in enumerate(reqs) if r.finished_by(t1)),
                   default=-1)
        judged = reqs[:last + 1]
        finished = [r for r in judged if r.finished_by(t1)]
        return {
            "judged": judged,
            "good": [r for r in finished if len(r.tokens) == r.out_len],
            "wrong": [r for r in reqs if len(r.tokens) > r.out_len
                      or (r.done and len(r.tokens) != r.out_len)],
            "passed_over": [r for r in judged if not r.started_by(t1)],
            "in_flight": [r for r in reqs
                          if r.started_by(t1) and not r.finished_by(t1)]}

    def _sample(self, good: List[Request]) -> List[Request]:
        """A seeded sample of finished requests with the longest in it."""
        k = int(self.cfg["check"]["sample_requests"])
        if len(good) <= k:
            return list(good)
        order = sorted(good, key=lambda r: (len(r.prompt) + r.out_len, r.rid))
        longest = order[-1]
        rest = [r for r in order if r is not longest]
        pick = traffic.rng_for(self.seed, "sample").choice(
            len(rest), size=k - 1, replace=False)
        return [longest] + [rest[int(i)] for i in sorted(pick)]

    def served_token_gaps(self, sample: List[Request], quants=()):
        """Over the sample's served tokens: (the gap of each served token
        below the reference's best, and for each lower precision in
        `quants` the gap of the token that precision puts first)."""
        served, low = [], {q: [] for q in quants}
        for r in sample:
            gaps, ctl = self.ref.served_token_gaps(
                self.params, self.cfg, r.prompt, r.tokens, quants=quants)
            served.append(gaps)
            for q in quants:
                low[q].append(ctl[q])
        return (np.concatenate(served),
                {q: np.concatenate(v) for q, v in low.items()})

    @staticmethod
    def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
        """The numbers a limit may be set on: the widest gap, the mean
        gap, and the share of tokens that are not the reference's best."""
        return {"served_token_gap_max": float(gaps.max()),
                "served_token_gap_mean": float(gaps.mean()),
                "served_token_off_best_pct": 100.0 * float((gaps > 0).mean())}

    def _served_token_checks(self, good: List[Request]) -> List[Check]:
        limits = self.cfg["check"]["limits"]
        sample = self._sample(good)
        if not sample:
            return [Check(name, float("inf"), float(limit), False)
                    for name, limit in sorted(limits.items())]
        t = time.perf_counter()
        gaps, _ = self.served_token_gaps(sample)
        harness.log(f"reference {len(sample)} requests, {len(gaps)} tokens, "
                    f"{time.perf_counter() - t:.1f} s")
        numbers = self.gap_numbers(gaps)
        return [at_most(name, numbers[name], float(limit))
                for name, limit in sorted(limits.items())]

    def control_readings(self, obs: dict, quants) -> dict:
        """Each number a limit may be set on, for the served tokens and
        for each lower-precision reference's own first choices, over the
        run's sample."""
        t1 = obs["t0"] + self.seconds
        sample = self._sample(self._judge(obs["reqs"], t1)["good"])
        gaps, low = self.served_token_gaps(sample, tuple(quants))
        out = {"tokens": int(len(gaps)), "sound": self.gap_numbers(gaps)}
        for q, g in low.items():
            out[q] = self.gap_numbers(g)
        return out

    def _readings(self, obs: dict) -> dict:
        snap = obs["snap"]
        block_bytes = (costs.lm_kv_bytes_per_token(self.cfg, POOL_DTYPE_BYTES)
                       * int(self.serving["block_size"]))
        t0 = obs["t0"]
        inside = sorted(t for r in obs["reqs"] for t in r.times
                        if t0 <= t < t0 + self.seconds)
        out = {"gen_lag_s": obs["lag"], "counters": snap,
               "kv_block_bytes": block_bytes,
               "answer_times": [t0] + inside + [t0 + self.seconds],
               "trace_window": obs["tw"], "kernel_calls": {},
               "host_spans": []}
        if obs["tw"] is None:
            return out
        live = [r for r in obs["reqs"] if r.times]
        decode, prefill = [], []

        def on_backend(label, ts, args):
            if label != "invoke":
                return
            if args.get("what") == "llm_decode":
                # context held at `ts` by the requests then in decode
                kv = sum(len(r.prompt) + bisect.bisect_right(r.times, ts)
                         for r in live
                         if r.times[0] <= ts
                         and not (r.done and r.times[-1] <= ts))
                decode.append(costs.lm_decode_step(
                    self.cfg, int(args["rows"]), kv + int(args["rows"])))
            elif args.get("what") == "llm_prefill":
                prefill.append(costs.lm_prefill(self.cfg, int(args["plen"])))

        out["host_spans"] = self._host_spans(obs, on_backend)
        out["kernel_calls"] = {"decode_step": decode, "prefill": prefill}
        harness.log("traced_calls " + json.dumps(
            {k: len(v) for k, v in out["kernel_calls"].items()}))
        return out
