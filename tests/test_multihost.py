"""Two-process multi-host runtime test (VERDICT r2 next #7).

The reference scales across hosts via NCCL/MPI inside the NN frameworks
plus its own TCP/MQTT transports; our DCN story is
`jax.distributed.initialize` + one global mesh (`parallel/multihost.py`,
SURVEY §5.8). Round 2 only ever exercised the single-process fallback —
this test runs the REAL multi-process path: two OS processes, a
localhost coordinator, 4 virtual CPU devices each → an 8-device global
mesh, a cross-process psum, and one `make_train_step` over dp=8 whose
gradient all-reduce spans both processes. Driver-style subprocess
harness (same pattern as `__graft_entry__._dryrun_in_subprocess`).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import json, os, sys
import numpy as np

import jax
import jax.numpy as jnp

pid = int(sys.argv[1])
coord = sys.argv[2]

from nnstreamer_tpu.parallel import multihost
joined = multihost.initialize(coordinator_address=coord,
                              num_processes=2, process_id=pid)
assert joined, "multi-process runtime did not start"
assert jax.process_count() == 2
assert len(jax.devices()) == 8, f"global devices {len(jax.devices())}"

from nnstreamer_tpu.parallel.mesh import MeshSpec
from nnstreamer_tpu.parallel.multihost import global_mesh
mesh = global_mesh(MeshSpec(dp=8))
assert mesh.devices.size == 8

# 1. cross-process collective: psum over dp of a per-device value.
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils

x_local = np.full((4, 1), float(pid + 1), np.float32)   # 4 local devices
x = multihost.host_local_batch(mesh, x_local)

@jax.jit
def total(v):
    return jnp.sum(v)

# sum over all 8 shards: 4*(1.0) + 4*(2.0) = 12
s = float(total(x))
assert abs(s - 12.0) < 1e-6, f"global sum {s}"

# 2. one train step across processes: dp=8 data-parallel gradient
# all-reduce spans the DCN boundary.
import optax
from nnstreamer_tpu.parallel.train import init_state, make_train_step

w0 = np.arange(4, dtype=np.float32).reshape(4, 1) / 10.0

def loss_fn(params, xb, yb):
    pred = xb @ params["w"]
    return jnp.mean((pred - yb) ** 2)

opt = optax.sgd(0.1)
params = {"w": jnp.asarray(w0)}
state = init_state(params, opt)
step = make_train_step(loss_fn, opt, mesh=mesh,
                       batch_spec=[P("dp"), P("dp")])

rng = np.random.RandomState(0)               # same data on both hosts
xb_all = rng.randn(16, 4).astype(np.float32)
yb_all = rng.randn(16, 1).astype(np.float32)
# each process owns its half of the global batch
xb, yb = multihost.host_local_batch(
    mesh, xb_all[pid * 8:(pid + 1) * 8], yb_all[pid * 8:(pid + 1) * 8])
state2, loss = step(state, xb, yb)
# params are replicated: every process holds the full array
w1 = np.asarray(state2.params["w"].addressable_shards[0].data)

# serial reference on the FULL batch must match the dp-sharded step
def ref_step(w):
    import numpy as _np
    pred = xb_all @ w
    grad = 2.0 * xb_all.T @ (pred - yb_all) / len(xb_all)
    return w - 0.1 * grad

w_ref = ref_step(w0)
err = float(np.abs(w1.reshape(4, 1) - w_ref).max())
assert err < 1e-5, f"train step mismatch {err}"

print(json.dumps({"pid": pid, "sum": s, "loss": float(loss),
                  "err": err}))
"""


def test_two_process_global_mesh_and_train_step(tmp_path):
    from conftest import free_port

    coord = f"127.0.0.1:{free_port()}"
    env = {k: v for k, v in os.environ.items()
           # TPU topology variables of the parent must not leak into
           # the pure-CPU worker processes
           if not k.startswith("TPU_")}
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=REPO,
    )
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = [
        subprocess.Popen([sys.executable, str(script), str(pid), coord],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert {o["pid"] for o in outs} == {0, 1}
    for o in outs:
        assert abs(o["sum"] - 12.0) < 1e-6
        assert o["err"] < 1e-5
    # both processes computed the identical global loss
    assert abs(outs[0]["loss"] - outs[1]["loss"]) < 1e-6
