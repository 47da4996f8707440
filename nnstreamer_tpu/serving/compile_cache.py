"""Persistent compile cache + bucket manifest for served models.

Two cooperating layers (docs/serving.md):

- **XLA executable cache**: jax's persistent compilation cache, so the
  *compilations* themselves survive process restarts.
- **Bucket manifest**: XLA's cache is keyed by HLO — it can only hit
  once something asks to compile. The manifest records *what to ask
  for*: every (model name, version) → the compile-bucket set it has
  served (dyn_batch pow2 buckets + fixed shapes). On the next process
  start, ``tensor_filter`` replays the manifest at element start()
  (backend ``warm_start``), compiling the whole working set off the
  hot path — against a warm XLA disk cache those are fast loads, not
  recompiles.

This module is the one place that decides where the cache lives
(`resolve_dir`): the directory ``JAX_COMPILATION_CACHE_DIR`` names —
jax reads that variable itself, so nothing here sets a directory —
else ``<checkout>/.jax_cache``, derived from this package's own
location. The path is part of the cache's key; one that moved with
the home directory, a pid or the time would never hit.

`enable_compile_cache()` turns it on (bench.py, chip_smoke.py);
`maybe_enable_compile_cache()` does so when the ``[serving]`` config
group opts in (``compile_cache=1``; env
``NNSTREAMER_TPU_SERVING_COMPILE_CACHE=1``), which is how ``store://``
filters reach it. Manifest writes are best-effort — the cache is an
optimization, never a gate.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional, Tuple

from nnstreamer_tpu.core.config import get_config
from nnstreamer_tpu.core.log import get_logger

log = get_logger("serving.cache")

_lock = threading.Lock()
_dir: Optional[str] = None          # set once the cache is on

#: <checkout>/.jax_cache (git-ignored): this file is
#: <checkout>/nnstreamer_tpu/serving/compile_cache.py
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def reset() -> None:
    """Forget that the cache was enabled (tests re-point it)."""
    global _dir
    with _lock:
        _dir = None


def cache_dir() -> Optional[str]:
    """The directory in use, None while the cache is off."""
    return _dir


def resolve_dir() -> Tuple[str, bool]:
    """(directory, from_env): where the cache and its manifest go, and
    whether ``JAX_COMPILATION_CACHE_DIR`` chose it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return (env, True) if env else (_CHECKOUT_DIR, False)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its
    directory. Idempotent. Raises OSError when the directory cannot be
    created."""
    global _dir
    with _lock:
        if _dir is not None:
            return _dir
        import jax

        d, from_env = resolve_dir()
        os.makedirs(d, exist_ok=True)
        if not from_env:
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        _dir = d
        log.info("persistent compile cache at %s", d)
        return d


def maybe_enable_compile_cache() -> bool:
    """Enable the cache when the ``[serving]`` config group opts in.
    Returns whether the cache is active."""
    if _dir is not None:
        return True
    if not get_config().get_bool("serving", "compile_cache", False):
        return False
    try:
        enable_compile_cache()
    except OSError as e:
        log.warning("compile cache disabled: %s", e)
        return False
    return True


# -- bucket manifest ---------------------------------------------------------
# Layout: <cache_dir>/manifest.json =
#   {"<name>@<version>": [{"kind": "dynb"|"fix", "nb": 8,
#                          "tensors": [{"shape": [...], "dtype": "f32"}]}]}

def _manifest_path() -> Optional[str]:
    return os.path.join(_dir, "manifest.json") if _dir else None


def _bucket_to_json(bk: tuple) -> Optional[dict]:
    kind = bk[0]
    if kind in ("llmp", "llmd", "llmp_chunk"):
        # LLM serving buckets (backends/llm_exec.py): prefill prompt
        # bucket / decode batch bucket / chunked-prefill chunk bucket —
        # one pow2 int, no tensor pairs
        return {"kind": kind, "n": int(bk[1])}
    if kind == "dynb":
        nb, pairs = bk[1], bk[2:]
    elif kind == "fix":
        nb, pairs = None, bk[1:]
    else:
        return None              # flexible seq/bat buckets: not replayed
    out = {"kind": kind,
           "tensors": [{"shape": list(s), "dtype": d} for s, d in pairs]}
    if nb is not None:
        out["nb"] = nb
    return out


def _bucket_from_json(obj: dict) -> Optional[tuple]:
    try:
        if obj["kind"] in ("llmp", "llmd", "llmp_chunk"):
            return (str(obj["kind"]), int(obj["n"]))
        pairs = tuple((tuple(t["shape"]), str(t["dtype"]))
                      for t in obj["tensors"])
        if obj["kind"] == "dynb":
            return ("dynb", int(obj["nb"])) + pairs
        if obj["kind"] == "fix":
            return ("fix",) + pairs
    except (KeyError, TypeError, ValueError):
        pass
    return None


def record_bucket(name: str, version: int, bucket_key: tuple) -> None:
    """Append one served bucket to the on-disk manifest (no-op when the
    cache is disabled). Called once per new bucket per process (the
    store entry dedups), so the read-modify-write stays cheap."""
    if not maybe_enable_compile_cache():
        return
    jb = _bucket_to_json(bucket_key)
    if jb is None:
        return
    path = _manifest_path()
    key = f"{name}@{version}"
    with _lock:
        try:
            data: Dict[str, list] = {}
            if os.path.exists(path):
                with open(path) as f:
                    data = json.load(f)
            rows = data.setdefault(key, [])
            if jb not in rows:
                rows.append(jb)
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(data, f, indent=1, sort_keys=True)
                os.replace(tmp, path)
        except Exception as e:
            log.warning("manifest write failed (%s@%d): %s",
                        name, version, e)


def manifest_buckets(name: str, version: int) -> List[tuple]:
    """The bucket set a previous process served for name@version, for
    warm-start replay. Empty when the cache is off or unseen."""
    if not maybe_enable_compile_cache():
        return []
    path = _manifest_path()
    try:
        if not os.path.exists(path):
            return []
        with open(path) as f:
            data = json.load(f)
        rows = data.get(f"{name}@{version}", [])
        out = [_bucket_from_json(r) for r in rows]
        return [b for b in out if b is not None]
    except Exception as e:
        log.warning("manifest read failed (%s@%d): %s", name, version, e)
        return []
