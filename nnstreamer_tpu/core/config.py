"""Layered configuration (reference: gst/nnstreamer/nnstreamer_conf.c).

Priority, highest first (nnsconf_loadconf:342-480 semantics):

1. Environment: ``NNSTREAMER_TPU_<GROUP>_<KEY>`` (e.g.
   ``NNSTREAMER_TPU_FILTER_DEFAULT_BACKEND=xla``), plus
   ``NNSTREAMER_TPU_PLUGINS`` as an extra plugin search path list.
2. Ini file: path from ``NNSTREAMER_TPU_CONF`` env, else
   ``~/.config/nnstreamer_tpu.ini``, else ``/etc/nnstreamer_tpu.ini``.
3. Built-in defaults.

Unlike the reference there is no dlopen .so scan: subplugins are python
modules. ``[common] plugin_paths`` lists directories whose ``*.py`` files
are imported on demand; importing a plugin module registers it (the
constructor-self-registration analog, nnstreamer_subplugin.c:111-131).
"""

from __future__ import annotations

import configparser
import os
import threading
from pathlib import Path
from typing import Dict, List, Optional

from nnstreamer_tpu.core.errors import ConfigError
from nnstreamer_tpu.core.log import get_logger

log = get_logger("config")

ENV_PREFIX = "NNSTREAMER_TPU_"
CONF_ENV = "NNSTREAMER_TPU_CONF"

_DEFAULTS: Dict[str, Dict[str, str]] = {
    "common": {
        "plugin_paths": "",
        "enable_envvar": "1",
    },
    "filter": {
        # backend auto-detect priority per model extension
        # (nnstreamer.ini.in framework_priority_* analog)
        "priority_stablehlo": "xla",
        "priority_msgpack": "xla",
        "priority_py": "custom",
        "default_backend": "xla",
    },
    "runtime": {
        "queue_capacity": "4",       # per-link buffer queue depth
        "drop_on_overrun": "0",      # leaky-queue behavior
        # scheduler-level chain fusion: run linear chains of cheap
        # single-in/single-out elements in one worker thread (direct
        # call-through, no channel hop per element)
        "chain_fusion": "1",
        # donate freshly-staged input buffers to bucketed XLA invokes
        # (HBM churn reduction; ignored on CPU where XLA aliases host
        # memory anyway)
        "donate_inputs": "1",
        # graph-level device segments: lower maximal linear
        # transform → filter [→ transform → filter]* [→ decoder(device)]
        # runs into ONE bucketed jit (graph/optimize.py fuse_segments)
        # so tensors stay in HBM with one dispatch per segment
        "device_segments": "1",
        # bounded async-dispatch window: max unresolved device results a
        # DEVICE_RESIDENT element may have in flight before the worker
        # blocks on the oldest (caps HBM held by live buffers); 0 = sync
        # after every dispatch
        "max_inflight": "8",
    },
    "serving": {
        # persistent XLA compile cache + bucket manifest for store://
        # models; opt-in. serving/compile_cache.py decides where it
        # lives ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
        "compile_cache": "0",
    },
}


class Config:
    def __init__(self, path: Optional[str] = None):
        self._lock = threading.Lock()
        self._ini: Dict[str, Dict[str, str]] = {}
        self._path = None
        candidates = (
            [path]
            if path
            else [
                os.environ.get(CONF_ENV),
                os.path.expanduser("~/.config/nnstreamer_tpu.ini"),
                "/etc/nnstreamer_tpu.ini",
            ]
        )
        for cand in candidates:
            if cand and Path(cand).is_file():
                self._load_ini(cand)
                self._path = cand
                break

    def _load_ini(self, path: str) -> None:
        parser = configparser.ConfigParser()
        try:
            parser.read(path)
        except configparser.Error as e:
            raise ConfigError(f"failed to parse config file {path}: {e}") from e
        for section in parser.sections():
            self._ini.setdefault(section.lower(), {}).update(
                {k.lower(): v for k, v in parser.items(section)}
            )
        log.debug("loaded config %s", path)

    # -- lookup ------------------------------------------------------------
    def get(self, group: str, key: str, default: Optional[str] = None) -> Optional[str]:
        """env > ini > built-in > `default`
        (nnsconf_get_custom_value_string:557 analog)."""
        group, key = group.lower(), key.lower()
        if self._envvar_enabled():
            env = os.environ.get(f"{ENV_PREFIX}{group.upper()}_{key.upper()}")
            if env is not None:
                return env
        if group in self._ini and key in self._ini[group]:
            return self._ini[group][key]
        return _DEFAULTS.get(group, {}).get(key, default)

    def get_bool(self, group: str, key: str, default: bool = False) -> bool:
        v = self.get(group, key)
        if v is None:
            return default
        return v.strip().lower() in ("1", "true", "yes", "on")

    def get_int(self, group: str, key: str, default: int = 0) -> int:
        v = self.get(group, key)
        if v is None:
            return default
        try:
            return int(v)
        except ValueError:
            raise ConfigError(
                f"config [{group}] {key}={v!r} is not an integer"
            ) from None

    def get_float(self, group: str, key: str, default: float = 0.0) -> float:
        v = self.get(group, key)
        if v is None:
            return default
        try:
            return float(v)
        except ValueError:
            raise ConfigError(
                f"config [{group}] {key}={v!r} is not a number"
            ) from None

    def plugin_paths(self) -> List[Path]:
        """Directories scanned for plugin modules (env paths first)."""
        paths: List[Path] = []
        env = os.environ.get(f"{ENV_PREFIX}PLUGINS", "")
        ini = self.get("common", "plugin_paths") or ""
        for chunk in (env, ini):
            for p in chunk.split(os.pathsep):
                if p.strip():
                    paths.append(Path(p.strip()).expanduser())
        return paths

    def _envvar_enabled(self) -> bool:
        # Note: consults ini/defaults directly to avoid recursion.
        v = self._ini.get("common", {}).get(
            "enable_envvar", _DEFAULTS["common"]["enable_envvar"]
        )
        return v.strip().lower() in ("1", "true", "yes", "on")

    def dump(self) -> str:
        """Human-readable effective config (nnsconf_dump:628 analog)."""
        lines = [f"# config file: {self._path or '(none)'}"]
        groups = sorted(set(_DEFAULTS) | set(self._ini))
        for g in groups:
            lines.append(f"[{g}]")
            keys = sorted(set(_DEFAULTS.get(g, {})) | set(self._ini.get(g, {})))
            for k in keys:
                lines.append(f"{k} = {self.get(g, k)}")
        return "\n".join(lines)


_global: Optional[Config] = None
_global_lock = threading.Lock()


def get_config() -> Config:
    global _global
    with _global_lock:
        if _global is None:
            _global = Config()
        return _global


def reset_config(path: Optional[str] = None) -> Config:
    """Replace the global config (tests / explicit re-load)."""
    global _global
    with _global_lock:
        _global = Config(path)
        return _global
