"""nnlint — per-rule fixtures, suppression/baseline machinery, and the
tier-1 gate that keeps the tree clean (docs/static_analysis.md).

Each rule gets a known-bad snippet it must fire on and a known-good one
it must stay silent on: the bad fixture pins the detector, the good one
pins the false-positive budget.  Fixtures are in-memory sources — the
linter is pure AST, nothing here is imported or executed.
"""

import json

import pytest

from nnstreamer_tpu.analysis import (
    SCHEMA_VERSION, element_contract, iter_rules, lint_report,
    load_baseline, project_from_sources, run_rules, write_baseline)
from nnstreamer_tpu.analysis.rules import ALL_RULES

REPO_PATHS = {
    "elem": "nnstreamer_tpu/elements/fix.py",
    "backend": "nnstreamer_tpu/backends/fix.py",
    "runtime": "nnstreamer_tpu/runtime/fix.py",
    "errors": "nnstreamer_tpu/core/errors.py",
}


def findings_for(rule_id, sources):
    project = project_from_sources(sources)
    report = run_rules(project, iter_rules([rule_id]))
    return report


def assert_fires(rule_id, sources, n_min=1):
    report = findings_for(rule_id, sources)
    assert len(report.findings) >= n_min, \
        f"{rule_id} should fire on the bad fixture"
    assert all(f.rule == rule_id for f in report.findings)
    return report.findings


def assert_silent(rule_id, sources):
    report = findings_for(rule_id, sources)
    assert report.clean, \
        f"{rule_id} false positives: {[str(f) for f in report.findings]}"


# -- NNL001 element-contract -------------------------------------------------

BAD_ELEMENT = '''
from nnstreamer_tpu.graph.pipeline import DYNAMIC, Element, SinkElement

class HalfTimer(Element):
    NUM_SINK_PADS = DYNAMIC
    def next_deadline(self):
        return None

class FusedTimer(Element):
    CHAIN_FUSABLE = True
    def next_deadline(self):
        return None
    def on_timer(self, now):
        pass

class ResidentSink(SinkElement):
    DEVICE_RESIDENT = True

class Mutator(Element):
    def __init__(self):
        self.CHAIN_FUSABLE = False
'''

GOOD_ELEMENT = '''
from nnstreamer_tpu.graph.pipeline import DYNAMIC, Element, SinkElement

class Batchy(Element):
    NUM_SINK_PADS = DYNAMIC
    CHAIN_FUSABLE = False
    def next_deadline(self):
        return None
    def on_timer(self, now):
        pass

class PlainSink(SinkElement):
    pass

class CallThrough(Element):
    NUM_SINK_PADS = 1
    NUM_SRC_PADS = 1
'''


def test_nnl001_fires_on_contract_violations():
    found = assert_fires("NNL001", {REPO_PATHS["elem"]: BAD_ELEMENT},
                         n_min=4)
    msgs = " ".join(f.message for f in found)
    assert "next_deadline without on_timer" in msgs
    assert "CHAIN_FUSABLE = False" in msgs
    assert "DEVICE_RESIDENT" in msgs
    assert "mutated per-instance" in msgs


def test_nnl001_silent_on_declared_contracts():
    assert_silent("NNL001", {REPO_PATHS["elem"]: GOOD_ELEMENT})


# -- NNL002 forced-sync ------------------------------------------------------

BAD_SYNC = '''
import jax
import numpy as np

def f(x):
    jax.block_until_ready(x)
    y = jax.device_get(x)
    return np.asarray(x)
'''

GOOD_SYNC = '''
import numpy as np
from nnstreamer_tpu.runtime.sync import device_sync

def f(x, tracer):
    out = np.asarray(device_sync(x, tracer=tracer, name="f"))
    table = np.asarray([1, 2], np.int32)   # 2-arg dtype conversion
    return out, table
'''


def test_nnl002_fires_on_direct_syncs():
    found = assert_fires("NNL002", {REPO_PATHS["backend"]: BAD_SYNC},
                         n_min=3)
    msgs = " ".join(f.message for f in found)
    assert "block_until_ready" in msgs
    assert "device_get" in msgs
    assert "np.asarray" in msgs


def test_nnl002_silent_on_device_sync_idiom():
    assert_silent("NNL002", {REPO_PATHS["backend"]: GOOD_SYNC})


def test_nnl002_asarray_scoped_to_device_layers():
    # elements/ consume host arrays the scheduler already resolved —
    # a bare asarray there is not a hidden sync
    assert_silent("NNL002", {
        REPO_PATHS["elem"]: "import numpy as np\n"
                            "def f(x):\n    return np.asarray(x)\n"})
    # runtime/sync.py itself is the one place the primitives live
    assert_silent("NNL002", {
        "nnstreamer_tpu/runtime/sync.py":
            "import jax\n"
            "def device_sync(t):\n"
            "    jax.block_until_ready(t)\n    return t\n"})


# -- NNL003 lock-discipline --------------------------------------------------

BAD_LOCK = '''
import time

class C:
    def f(self):
        with self._lock:
            time.sleep(0.1)

    def g(self, q):
        with self._state_lock:
            return q.get(timeout=1.0)

    def h(self, t):
        with self._lock:
            t.join()
'''

GOOD_LOCK = '''
import time

class C:
    def f(self):
        with self._lock:
            snapshot = dict(self._state)
        time.sleep(0.1)                     # blocking OUTSIDE the lock
        return snapshot

    def g(self):
        with self._lock:
            v = self._cache.get("key")      # dict.get, not a queue
        return v

    def h(self, data):
        with self.send_lock:
            self.sock.sendall(data)         # write-serialization lock

    def i(self, cv):
        with self._lock:
            def cb():
                time.sleep(1)               # nested def: not run here
            return cb
'''


def test_nnl003_fires_on_blocking_under_lock():
    found = assert_fires("NNL003", {REPO_PATHS["runtime"]: BAD_LOCK},
                         n_min=3)
    msgs = " ".join(f.message for f in found)
    assert "time.sleep" in msgs
    assert "queue/channel get()" in msgs
    assert "join" in msgs


def test_nnl003_silent_on_disciplined_locking():
    assert_silent("NNL003", {REPO_PATHS["runtime"]: GOOD_LOCK})


# -- NNL004 jit-purity -------------------------------------------------------

BAD_JIT = '''
import time
import jax

def impure(x):
    return x * time.time()

fast = jax.jit(impure)

@jax.jit
def also_impure(x):
    import random
    return x + random.random()
'''

BAD_JIT_CROSS_MAIN = '''
import jax
from nnstreamer_tpu.jhelp import helper

fast = jax.jit(helper)
'''

BAD_JIT_CROSS_HELPER = '''
import time

def helper(x):
    return x * time.perf_counter()
'''

GOOD_JIT = '''
import jax
import jax.numpy as jnp

def pure(x):
    return jnp.tanh(x) * 2.0

fast = jax.jit(pure)

@jax.jit
def also_pure(x):
    return pure(x) + 1.0
'''


def test_nnl004_fires_on_impure_jit():
    found = assert_fires("NNL004", {REPO_PATHS["runtime"]: BAD_JIT},
                         n_min=2)
    msgs = " ".join(f.message for f in found)
    assert "time.time" in msgs
    assert "random.random" in msgs


def test_nnl004_follows_cross_module_imports():
    assert_fires("NNL004", {
        REPO_PATHS["runtime"]: BAD_JIT_CROSS_MAIN,
        "nnstreamer_tpu/jhelp.py": BAD_JIT_CROSS_HELPER})


def test_nnl004_silent_on_pure_jit():
    assert_silent("NNL004", {REPO_PATHS["runtime"]: GOOD_JIT})


# -- NNL005 spawn-safety -----------------------------------------------------

WORKER = "nnstreamer_tpu/serving/worker.py"

BAD_SPAWN = {
    WORKER: "from nnstreamer_tpu.serving import spawn_helper\n",
    "nnstreamer_tpu/serving/spawn_helper.py":
        "import jax\n"
        "WARM = jax.jit(lambda x: x)\n",
}

GOOD_SPAWN = {
    WORKER: "from nnstreamer_tpu.serving import spawn_helper\n",
    "nnstreamer_tpu/serving/spawn_helper.py":
        "def warm(x):\n"
        "    import jax\n"          # lazy: runs on first call, not import
        "    return jax.jit(lambda y: y)(x)\n",
}


def test_nnl005_fires_on_module_scope_jax_in_worker_closure():
    found = assert_fires("NNL005", BAD_SPAWN, n_min=2)
    assert {f.path for f in found} == \
        {"nnstreamer_tpu/serving/spawn_helper.py"}


def test_nnl005_silent_on_lazy_imports():
    assert_silent("NNL005", GOOD_SPAWN)


def test_nnl005_ignores_modules_outside_the_closure():
    # same jax-at-import sin, but nothing the worker imports
    assert_silent("NNL005", {
        WORKER: "import os\n",
        "nnstreamer_tpu/elements/heavy.py": "import jax\n"})


# -- NNL006 picklable-errors -------------------------------------------------

BAD_ERRORS = '''
class NakedError(Exception):
    def __init__(self, what, code):
        super().__init__(f"{what} [{code}]")
'''

GOOD_ERRORS = '''
def _rebuild(cls, args):
    return cls.__new__(cls)

class BaseError(Exception):
    def __reduce__(self):
        return (_rebuild, (type(self), self.args))

class ChildError(BaseError):
    def __init__(self, what, code):
        super().__init__(f"{what} [{code}]")

class _PrivateScratch(Exception):
    pass

class NotAnError:
    pass
'''


def test_nnl006_fires_on_unpicklable_error():
    found = assert_fires("NNL006", {REPO_PATHS["errors"]: BAD_ERRORS})
    assert "NakedError" in found[0].message


def test_nnl006_silent_on_reduce_chain():
    assert_silent("NNL006", {REPO_PATHS["errors"]: GOOD_ERRORS})


def test_nnl006_only_checks_errors_modules():
    assert_silent("NNL006", {REPO_PATHS["runtime"]: BAD_ERRORS})


# -- NNL007 thread-audit -----------------------------------------------------

BAD_THREAD = '''
import threading

def fire_and_forget(fn):
    threading.Thread(target=fn).start()
    threading.Timer(5.0, fn).start()
'''

GOOD_THREAD = '''
import threading

class Owner:
    def start(self, fn):
        self._t = threading.Thread(target=fn, daemon=True)
        self._t.start()
        self._timer = threading.Timer(5.0, fn)
        self._timer.daemon = True
        self._timer.start()
        self._j = threading.Thread(target=fn)
        self._j.start()

    def close(self):
        self._timer.cancel()
        self._j.join()

class Looper(threading.Thread):
    def __init__(self):
        super().__init__(daemon=True)
'''


def test_nnl007_fires_on_orphan_threads():
    assert_fires("NNL007", {REPO_PATHS["runtime"]: BAD_THREAD}, n_min=2)


def test_nnl007_silent_on_owned_threads():
    assert_silent("NNL007", {REPO_PATHS["runtime"]: GOOD_THREAD})


# -- NNL008 socket-audit -----------------------------------------------------

EDGE_PATH = "nnstreamer_tpu/edge/fix.py"

BAD_SOCKET = '''
import socket

def dial(host, port):
    return socket.create_connection((host, port))   # unbounded dial

class Poller:
    def __init__(self):
        self._sock = socket.socket()                # no deadline, no owner

    def poll(self):
        return self._sock.recv(4)
'''

GOOD_SOCKET = '''
import socket
import threading

def dial(host, port):
    return socket.create_connection((host, port), 5.0)

def dial_kw(host, port):
    return socket.create_connection((host, port), timeout=5.0)

class Poller:
    def __init__(self):
        self._sock = socket.socket()
        self._sock.settimeout(2.0)                  # bounded

class Server:
    def __init__(self):
        self._srv = socket.socket()                 # accept-thread-owned
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True)
        self._thread.start()

    def _accept_loop(self):
        while True:
            self._srv.accept()
'''


def test_nnl008_fires_on_unbounded_sockets():
    findings = assert_fires("NNL008", {EDGE_PATH: BAD_SOCKET}, n_min=2)
    msgs = " | ".join(f.message for f in findings)
    assert "connect timeout" in msgs       # the dial arm
    assert "deadline discipline" in msgs   # the raw-socket arm


def test_nnl008_silent_on_bounded_or_thread_owned():
    assert_silent("NNL008", {EDGE_PATH: GOOD_SOCKET})


def test_nnl008_scoped_to_serving_path():
    # the same unbounded sockets outside edge/serving/traffic are
    # someone else's problem (tooling, tests, offline scripts)
    assert_silent("NNL008", {REPO_PATHS["runtime"]: BAD_SOCKET})


# -- NNL009 placement-audit --------------------------------------------------

BAD_PLACEMENT = '''
import jax

def pin():
    d = jax.devices()[0]                 # explicit ordinal pick
    e = jax.local_devices()[2]
    return d, e
'''

GOOD_PLACEMENT = '''
import jax

def enumerate_all():
    n = len(jax.devices())               # counting is fine
    head = jax.devices()[:n]             # slices keep the set, not a pick
    return head
'''


def test_nnl009_fires_on_explicit_device_pick():
    findings = assert_fires(
        "NNL009", {REPO_PATHS["backend"]: BAD_PLACEMENT}, n_min=2)
    assert all("placement" in f.message for f in findings)


def test_nnl009_silent_on_enumeration_and_slices():
    assert_silent("NNL009", {REPO_PATHS["backend"]: GOOD_PLACEMENT})


def test_nnl009_blessed_in_placement_and_parallel():
    # serving/placement.py and parallel/ ARE the placement subsystem —
    # the rule exists to keep device picks from leaking anywhere else
    assert_silent("NNL009", {
        "nnstreamer_tpu/serving/placement.py": BAD_PLACEMENT,
        "nnstreamer_tpu/parallel/mesh.py": BAD_PLACEMENT,
    })


# -- NNL010 device-accounting ------------------------------------------------

BAD_ACCOUNTING = '''
import jax

PEAK_BF16_TFLOPS = 275.0                 # second peak table: drift bait

def probe(jitted, args):
    cost = jitted.lower(*args).cost_analysis()   # cost-model read
    ms = jax.devices()[0].memory_stats()         # memory ledger read
    return cost, ms
'''

GOOD_ACCOUNTING = '''
from nnstreamer_tpu.runtime import devprof

def probe(jitted, args, dt):
    prof = devprof.get()
    prof.capture_cost("f", "static", jitted, args, seconds=dt)
    return prof.stats()
'''


def test_nnl010_fires_on_accounting_outside_devprof():
    findings = assert_fires(
        "NNL010", {REPO_PATHS["backend"]: BAD_ACCOUNTING}, n_min=3)
    msgs = " ".join(f.message for f in findings)
    assert "cost_analysis" in msgs and "memory_stats" in msgs
    assert "PEAK_BF16_TFLOPS" in msgs


def test_nnl010_silent_on_profiler_reporting():
    assert_silent("NNL010", {REPO_PATHS["backend"]: GOOD_ACCOUNTING})


def test_nnl010_blessed_in_devprof_and_bench():
    # runtime/devprof.py IS the accounting site; bench.py keeps its
    # sweep-local peak table by design (it lives outside the package)
    assert_silent("NNL010", {
        "nnstreamer_tpu/runtime/devprof.py": BAD_ACCOUNTING,
        "bench.py": BAD_ACCOUNTING,
    })


# -- NNL011 seeded-chaos -----------------------------------------------------

BAD_CHAOS_RNG = '''
import random
import numpy as np

def schedule_faults():
    jitter = random.Random()                 # OS-entropy: no replay
    rng = np.random.default_rng()            # ditto
    return jitter.random(), rng.random()
'''

GOOD_CHAOS_RNG = '''
import random
import numpy as np

def schedule_faults(seed):
    jitter = random.Random(seed)
    rng = np.random.default_rng(seed + 1)
    kw = np.random.default_rng(seed=seed)
    return jitter.random(), rng.random(), kw.random()
'''


def test_nnl011_fires_on_unseeded_rng_in_chaos_paths():
    for path in ("nnstreamer_tpu/traffic/fix.py",
                 "nnstreamer_tpu/scenario/fix.py",
                 "nnstreamer_tpu/serving/worker.py"):
        findings = assert_fires("NNL011", {path: BAD_CHAOS_RNG},
                                n_min=2)
        msgs = " ".join(f.message for f in findings)
        assert "random.Random" in msgs and "default_rng" in msgs


def test_nnl011_silent_on_seeded_rng():
    assert_silent("NNL011",
                  {"nnstreamer_tpu/traffic/fix.py": GOOD_CHAOS_RNG})


def test_nnl011_silent_outside_the_chaos_paths():
    # an unseeded rng elsewhere is someone else's design decision
    assert_silent("NNL011", {REPO_PATHS["backend"]: BAD_CHAOS_RNG,
                             REPO_PATHS["elem"]: BAD_CHAOS_RNG})


# -- NNL012 shard-safety -----------------------------------------------------

BAD_SHARDING = '''
import jax
from jax.sharding import NamedSharding, PartitionSpec

def place(mesh, tree, fn):
    spec = PartitionSpec("tp")                       # private mesh program
    placed = jax.device_put(tree, NamedSharding(mesh, spec))
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                         out_specs=spec)(placed)
'''

GOOD_SHARDING = '''
from nnstreamer_tpu.serving import sharding

def place(params, mesh, n_heads):
    placed, specs = sharding.shard_llm_params(params, mesh,
                                              n_heads=n_heads)
    return placed, sharding.kv_pool_placer(mesh)
'''


def test_nnl012_fires_on_mesh_program_outside_subsystem():
    findings = assert_fires(
        "NNL012", {REPO_PATHS["backend"]: BAD_SHARDING}, n_min=4)
    msgs = " ".join(f.message for f in findings)
    # both arms: the jax import and every construction site
    assert "from jax.sharding import" in msgs
    assert "shard_map" in msgs and "NamedSharding" in msgs \
        and "PartitionSpec" in msgs


def test_nnl012_silent_on_consuming_the_subsystem():
    assert_silent("NNL012", {REPO_PATHS["backend"]: GOOD_SHARDING})


def test_nnl012_blessed_in_parallel_and_sharding():
    # parallel/ and serving/sharding.py ARE the sharding subsystem —
    # the rule keeps private mesh programs from leaking anywhere else
    assert_silent("NNL012", {
        "nnstreamer_tpu/serving/sharding.py": BAD_SHARDING,
        "nnstreamer_tpu/parallel/ring_attention.py": BAD_SHARDING,
        "nnstreamer_tpu/parallel/moe.py": BAD_SHARDING,
    })


# -- NNL013 shm-safety -------------------------------------------------------

BAD_SHM = '''
import mmap
import pickle
from multiprocessing import shared_memory

def open_segment(name, frames):
    seg = shared_memory.SharedMemory(name=name, create=True, size=4096)
    ring = mmap.mmap(-1, 4096)                     # second lifetime story
    for f in frames:
        blob = pickle.dumps(f)                     # per-frame re-serialize
        seg.buf[:len(blob)] = blob
    return seg, ring
'''

GOOD_SHM = '''
import pickle
from nnstreamer_tpu.serving.shm import ShmRing, ring_name

def open_rings(pool, wid, spawn, frames):
    ring = ShmRing.create(ring_name("rq", pool, wid, spawn))
    blob = pickle.dumps(frames)          # hoisted: once per batch
    for _ in frames:
        ring.try_write(blob)
    return ring
'''


def test_nnl013_fires_on_segment_lifetime_outside_shm_module():
    findings = assert_fires(
        "NNL013", {"nnstreamer_tpu/serving/fix.py": BAD_SHM}, n_min=4)
    msgs = " ".join(f.message for f in findings)
    # all three arms: the import, each construction site, and the
    # per-frame pickle.dumps in the hot loop
    assert "multiprocessing.shared_memory" in msgs
    assert "SharedMemory" in msgs and "mmap.mmap" in msgs
    assert "pickle.dumps" in msgs


def test_nnl013_silent_on_routing_through_shm_ring():
    assert_silent("NNL013",
                  {"nnstreamer_tpu/serving/fix.py": GOOD_SHM})


def test_nnl013_blessed_in_the_shm_module_itself():
    # serving/shm.py IS the lifetime owner — the rule keeps segments
    # from being constructed anywhere else. (The hot-loop pickle arm
    # still applies there, so strip the loop body for this fixture.)
    segments_only = BAD_SHM.replace("blob = pickle.dumps(f)",
                                    "blob = bytes(f)")
    assert_silent("NNL013",
                  {"nnstreamer_tpu/serving/shm.py": segments_only})


def test_nnl013_per_frame_pickle_scoped_to_serving():
    # a pickle loop outside serving/ is someone else's trade-off; the
    # segment-construction arm still applies everywhere
    assert_silent("NNL013", {REPO_PATHS["runtime"]: GOOD_SHM})
    findings = assert_fires("NNL013", {REPO_PATHS["runtime"]: BAD_SHM},
                            n_min=3)
    assert not any("pickle.dumps" in f.message for f in findings)


# -- suppressions ------------------------------------------------------------

def test_inline_suppression_waives_a_finding():
    src = BAD_SYNC.replace(
        "jax.block_until_ready(x)",
        "jax.block_until_ready(x)  # nnlint: disable=NNL002 warm path")
    report = findings_for("NNL002", {REPO_PATHS["backend"]: src})
    assert len(report.suppressed) == 1
    assert report.suppressed[0].rule == "NNL002"
    # the other two sites still fire
    assert len(report.findings) == 2


def test_disable_all_and_unrelated_rule():
    src = ("import time\n"
           "class C:\n"
           "    def f(self):\n"
           "        with self._lock:\n"
           "            time.sleep(1)  # nnlint: disable=all wedge drill\n")
    assert_silent("NNL003", {REPO_PATHS["runtime"]: src})
    src_wrong = src.replace("disable=all", "disable=NNL001")
    report = findings_for("NNL003", {REPO_PATHS["runtime"]: src_wrong})
    assert len(report.findings) == 1   # NNL001 disable does not cover 003


# -- baseline ----------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    sources = {REPO_PATHS["backend"]: BAD_SYNC}
    report = findings_for("NNL002", sources)
    assert not report.clean
    bl = tmp_path / "baseline.json"
    write_baseline(bl, report.findings)
    report2 = run_rules(project_from_sources(sources),
                        iter_rules(["NNL002"]), load_baseline(bl))
    assert report2.clean
    assert report2.baselined == len(report.findings)


def test_fingerprint_survives_line_shifts(tmp_path):
    report = findings_for("NNL002", {REPO_PATHS["backend"]: BAD_SYNC})
    bl = tmp_path / "baseline.json"
    write_baseline(bl, report.findings)
    shifted = "# one\n# two\n# three\n" + BAD_SYNC
    report2 = run_rules(
        project_from_sources({REPO_PATHS["backend"]: shifted}),
        iter_rules(["NNL002"]), load_baseline(bl))
    assert report2.clean, "baseline must match across pure line shifts"


# -- report schema / rule catalog -------------------------------------------

def test_json_report_schema():
    report = findings_for("NNL002", {REPO_PATHS["backend"]: BAD_SYNC})
    d = json.loads(json.dumps(report.to_json()))
    assert d["version"] == SCHEMA_VERSION
    assert set(d) == {"version", "clean", "files", "rules", "counts",
                      "baselined", "suppressed", "findings"}
    assert d["clean"] is False
    assert d["counts"] == {"NNL002": len(d["findings"])}
    for f in d["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message",
                          "fingerprint", "suppressed"}
        assert f["line"] > 0 and len(f["fingerprint"]) == 16


def test_rule_catalog_complete():
    ids = [r.rule_id for r in ALL_RULES]
    assert ids == sorted(set(ids)), "rule ids unique and ordered"
    assert len(ids) >= 7
    for r in ALL_RULES:
        assert r.title and r.rationale
    with pytest.raises(ValueError):
        iter_rules(["NNL999"])


def test_syntax_error_becomes_nnl000(tmp_path):
    from nnstreamer_tpu.analysis.core import build_project
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    p = build_project([str(bad)], root=tmp_path)
    r = run_rules(p, iter_rules(None))
    assert [f.rule for f in r.findings] == ["NNL000"]


# -- contract introspection (docs + linter share one truth) ------------------

def test_element_contract_introspection():
    from nnstreamer_tpu.elements.batch import TensorBatch
    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.elements.routing import Tee

    c = element_contract(TensorBatch)
    assert c["timer"] is True
    assert c["chain_fusable"] is False
    assert c["sink_pads"] == "dynamic"

    c = element_contract(TensorFilter)
    assert c["device_resident"] is True
    assert c["chain_fusable"] is False

    c = element_contract(Tee)
    assert c["timer"] is False
    assert c["src_pads"] == "dynamic"


# -- the tier-1 gate ---------------------------------------------------------

def test_tree_is_lint_clean():
    """The whole package must lint clean against the committed (empty)
    baseline: new findings are fixed or inline-justified, never
    accumulated."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    report = lint_report(["nnstreamer_tpu"], root=root,
                         baseline_path=root / "nnlint_baseline.json")
    assert report.files > 100
    assert report.clean, "unbaselined findings:\n" + "\n".join(
        str(f) for f in report.findings)
    assert report.baselined == 0, \
        "the committed baseline must stay empty (fix or inline-suppress)"
