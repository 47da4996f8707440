"""The seam between the executor and a model family (llm/families.py):
a family the program does not have is served through the executor and
the engine as they are, an unknown one is refused, and every family's
`stats()` holds what the benchmark's runners read."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))

import tiny_sparse_moe as tiny                                  # noqa: E402
from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor   # noqa: E402
from nnstreamer_tpu.backends.xla import ModelBundle             # noqa: E402
from nnstreamer_tpu.core.errors import BackendError             # noqa: E402
from nnstreamer_tpu.llm import families, paged_model            # noqa: E402
from nnstreamer_tpu.llm.engine import LLMEngine                 # noqa: E402
from nnstreamer_tpu.llm.spec import LMSpec                      # noqa: E402
from nnstreamer_tpu.models.transformer import init_params       # noqa: E402
from nnstreamer_tpu.runtime.tracing import Tracer               # noqa: E402
from perfbench.references import sparse_moe_lm as ref           # noqa: E402
from perfbench.runners.sparse_moe_llm import (                  # noqa: E402
    EXECUTOR_COUNTERS, lm_spec)

SERVING = dict(block_size=8, num_blocks=40, max_len=64, max_batch=2,
               prefill_chunk=8)


@pytest.fixture(scope="module")
def dense():
    return init_params(vocab=61, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, seed=3)


# -- a third family, known to this file alone --------------------------------
# The dense programs under names of their own; each returns, beside its
# logits, the depth of context its call leaves behind.

def echo_prefill(params, ids, blk_idx, blk_off, k_pool, v_pool, last_idx,
                 *, n_heads, dtype):
    logits, k, v = paged_model.paged_prefill(
        params, ids, blk_idx, blk_off, k_pool, v_pool, last_idx,
        n_heads=n_heads, dtype=dtype)
    return logits, last_idx + 1, k, v


def echo_prefill_chunk(params, ids, pos0, blk_idx, blk_off, table, k_pool,
                       v_pool, last_idx, *, n_heads, dtype):
    logits, k, v = paged_model.paged_prefill_chunk(
        params, ids, pos0, blk_idx, blk_off, table, k_pool, v_pool,
        last_idx, n_heads=n_heads, dtype=dtype)
    return logits, pos0 + last_idx + 1, k, v


def echo_decode_step(params, cur, tables, pos, k_pool, v_pool,
                     *, n_heads, dtype):
    logits, k, v = paged_model.paged_decode_step(
        params, cur, tables, pos, k_pool, v_pool, n_heads=n_heads,
        dtype=dtype)
    return logits, pos + 1, k, v


class EchoSet(families.DenseSet):
    family = "echo"
    FNS = {"prefill": echo_prefill, "chunk": echo_prefill_chunk,
           "decode": echo_decode_step}

    def __init__(self, spec, **given):
        super().__init__(spec, **given)
        self.counters.update(echo_values=0, echo_depth_sum=0)

    def program(self, kind):
        return super().program(kind)._replace(fn=self.FNS[kind])

    def split(self, out):
        logits, depth, *pools = out
        return logits, (depth,), pools

    def note_beside(self, kind, host, bucket=0):
        depth = int(np.max(host[0]))
        self.counters["echo_values"] += 1
        self.counters["echo_depth_sum"] += depth
        return {"echo_depth": depth}

    def stats(self):
        return dict(self.counters, family=self.family)


def _serve(model, **kw):
    eng = LLMEngine(model, n_heads=4, **SERVING, **kw)
    rng = np.random.default_rng(7)
    reqs = [eng.submit(rng.integers(1, 60, n).astype(np.int32),
                       max_new_tokens=m) for n, m in ((5, 9), (21, 6))]
    eng.drain()
    return eng, [list(r.tokens) for r in reqs]


def test_a_family_registered_here_is_served_by_the_engine_as_it_is(
        dense, monkeypatch):
    """A whole prompt, a chunked one and their decode steps launched
    ahead: the dense family's tokens, and what each chunk and step
    returned beside its logits on its span and in the set's counter."""
    monkeypatch.setitem(families.FAMILIES, "echo", EchoSet)
    bundle = ModelBundle(fn=None, params=dense, lm=LMSpec(
        family="echo", n_heads=4, n_kv=2, head_dim=16))
    tracer = Tracer(max_events=4096)
    _serve(bundle)                                   # compiles
    eng, tokens = _serve(bundle, tracer=tracer)
    _, want = _serve(dense)
    assert tokens == want and [len(t) for t in tokens] == [9, 6]
    assert isinstance(eng.executor.programs, EchoSet)
    st = eng.stats()
    ex = st["executor"]
    assert st["lookahead_steps"] > 0 and ex["family"] == "echo"
    # every chunk's and every step's value was read; the whole prompt's
    # (one `prefill`) is dropped
    assert ex["prefills"] == 1 and ex["chunk_prefills"] == 3
    assert ex["echo_values"] == ex["decode_steps"] + ex["chunk_prefills"]
    spans = [(label, args) for ph, cat, _, label, _, _, args
             in tracer.events() if ph == "X" and cat == "backend" and args]
    chunks = [a for label, a in spans if label == "resolve"]
    assert [(c["pos0"], c["clen"]) for c in chunks] == [(0, 8), (8, 8),
                                                        (16, 5)]
    assert [c["echo_depth"] for c in chunks] == [8, 16, 21]
    steps = [a for label, a in spans if label == "invoke"
             and a.get("what") == "llm_decode"]
    # the first call of the bucket of one row wrote a `compile` span in
    # its place; the bucket of two was built where the two were admitted
    assert len(steps) == ex["decode_steps"] - 1
    # the deepest row of a step: its context with the step's own token
    assert all(1 <= a["echo_depth"] <= a["kv_tokens"] for a in steps)
    assert steps[0]["rows"] == 1
    assert steps[0]["echo_depth"] == steps[0]["kv_tokens"] == 7
    assert ex["echo_depth_sum"] > sum(
        a["echo_depth"] for a in chunks + steps)


@pytest.mark.parametrize("through", [PagedLLMExecutor, LLMEngine])
def test_an_unknown_family_is_refused_at_construction(dense, through):
    bundle = ModelBundle(fn=None, params=dense, lm=LMSpec(
        family="hyena", n_heads=4, n_kv=2, head_dim=16))
    with pytest.raises(BackendError, match="family 'hyena'.*dense"):
        through(bundle, block_size=8, num_blocks=16, max_len=32)


# -- what the benchmark's runners read ---------------------------------------

#: perfbench/runners/llm.py `_counters`: of the engine, of the executor
ENGINE_KEYS = ("tokens_out", "steps", "admission_blocked")
EXECUTOR_KEYS = ("compile_count", "decode_steps", "prefills")


def _sparse_bundle():
    return ModelBundle(
        fn=None, lm=lm_spec(tiny.CONFIG),
        params=ref.make_params(tiny.CONFIG, 2**31 + 5, dtype=jnp.float32))


@pytest.mark.parametrize("family,extra", [
    ("dense", ()), ("sparse_moe", EXECUTOR_COUNTERS)])
def test_stats_hold_the_keys_the_runners_read(dense, family, extra):
    model = dense if family == "dense" else _sparse_bundle()
    eng = LLMEngine(model, n_heads=4, dtype=jnp.float32, **SERVING)
    assert eng.executor.programs.family == family
    eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    eng.drain()
    st = eng.stats()
    ex = st["executor"]
    assert all(isinstance(st[k], int) for k in ENGINE_KEYS)
    assert all(isinstance(ex[k], int) for k in EXECUTOR_KEYS + extra)
    assert st["cache"]["blocks_used"] == 0 and ex["decode_steps"] == 2
    assert ("family" in ex) == (family != "dense")
    # the compiled window's five keys went with it (what a request waits
    # for in a family with window pools is another window: PR 39)
    assert not [k for k in set(st) | set(ex)
                if "window" in k and k != "admission_blocked_window"]
