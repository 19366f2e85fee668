"""Process-level JAX placement: which backend, and where compiles are cached.

Two helpers that must run BEFORE the first JAX backend touch, shared by
tests/conftest.py, __graft_entry__.py and chip_smoke.py, plus the on-disk compile-cache hardening every process
that reads or writes cache entries installs.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Decide where this process tree keeps JAX's persistent compile cache.

    Call first thing, before jax is imported. An externally set
    ``JAX_COMPILATION_CACHE_DIR`` wins and is left untouched; otherwise
    the variable is set to ``<checkout>/.jax_cache`` — a FIXED path (the
    directory is part of the cache key, so a temp name, pid or timestamp
    would never hit). JAX reads the variable itself; nothing here or
    anywhere else in the repo sets a cache directory through
    ``jax.config``. Raylet-spawned workers inherit the environment, so
    every process of a run shares the one directory. Returns it."""
    cache_dir = os.environ.get(_CACHE_ENV)
    if not cache_dir:
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
        os.environ[_CACHE_ENV] = cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    return cache_dir


def force_cpu_devices(n_devices: int = 8) -> None:
    """Pin jax to the CPU platform with >= n_devices virtual devices.

    The CPU rehearsal switch: tests and ``__graft_entry__.py`` use it so
    N XLA host devices stand in for N chips (meshes, shardings,
    collectives) without an accelerator.
    Chip paths never call it. Must run before the first backend touch
    (jax import is fine). Idempotent; raises if the backend already
    exists on another platform (nothing can be done then).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --{_FLAG}={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = re.sub(
            rf"--{_FLAG}=\d+", f"--{_FLAG}={n_devices}", flags
        )
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend may already be initialized; verified below
    # Loudly verify the pin took — config.update silently loses the race if
    # the backend was already created (e.g. entry() ran first), and a "CPU
    # dry-run" silently executing on real hardware must never happen.
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"force_cpu_devices: backend already initialized on {platform!r}; "
            "call before any jax backend touch"
        )


# Cached-executable keys the persistent compile cache must never serve
# or store ON THE CPU BACKEND, matched by prefix (the key is
# "<jitted fn name>-<hash>"). An earlier jaxlib's CPU backend corrupted
# the glibc heap DESERIALIZING some program shapes back from the cache —
# "corrupted double-linked list" / segfault far from the cache, on the
# first warm run only, while the cold compile of the identical program
# is fine (repro: tests/test_rllib.py). Isolated by delete-entry /
# restore-entry A/B on the cache dir: confirmed crashers were PPO's
# donated sgd `epoch` (rllib/ppo_core) and A2C's donated `_update_impl`;
# the whole rllib donated-train-step family is blocklisted because every
# member shares the shape that crashed (donated bound-method step, small
# net, unrolled scan) and recompiling any of them costs ~1 s. Not shown
# to be gone on the installed jaxlib 0.9.0, so the guard stays — but it
# is a CPU-backend guard only: a process whose backend is a TPU caches
# every program, whatever its name (see `_blocked_key`). A config-flag
# opt-out cannot work per-program: jax memoizes `is_cache_used` per
# process at first cache touch. Extend via the
# RAY_TPU_JAX_CACHE_BLOCKLIST env var (comma-separated prefixes).
_CACHE_KEY_BLOCKLIST = (
    "jit_epoch-",
    "jit__update_impl-",
    "jit__update-",
    "jit_update-",
    "jit_apply_fn-",
    "jit_rq_step-",
    "jit__step_impl-",
)


def _blocked_key(key: str) -> bool:
    import os as _os

    import jax

    # The cache is only touched while compiling, so the backend exists.
    if jax.default_backend() != "cpu":
        return False
    extra = _os.environ.get("RAY_TPU_JAX_CACHE_BLOCKLIST", "")
    prefixes = _CACHE_KEY_BLOCKLIST + tuple(
        p.strip() for p in extra.split(",") if p.strip())
    return key.startswith(prefixes)


def harden_jax_compilation_cache() -> None:
    """Two fixes to jax's on-disk compile cache (checked against the
    installed 0.9.0), patched in place.

    1. ATOMIC WRITES: ``LRUCache.put`` still stores the serialized
       executable with a bare ``Path.write_bytes``. A process hard-killed
       mid-write
       — the test tier's timeout SIGKILL, an XLA CHECK-failure abort —
       can leave a TRUNCATED ``-cache`` file for the next session to
       deserialize. ``rename()`` is atomic on the same filesystem, so
       readers observe the old state or the whole new entry, never a
       torn one.

    2. KEY BLOCKLIST (CPU backend only): programs whose cached
       executables crashed jaxlib's CPU backend on deserialization (see
       ``_CACHE_KEY_BLOCKLIST`` above) are neither stored nor served —
       gating ``get`` too means a poisonous entry left by a pre-fix run
       is inert, not a landmine. Programs compiled for a TPU backend
       are never blocked.

    Call once per process that might touch cache entries (the test
    harness and cluster workers both do). No-op when jax's private cache
    layout has moved."""
    import os as _os

    try:
        from jax._src import lru_cache as _lru

        cache_suffix = _lru._CACHE_SUFFIX
        atime_suffix = _lru._ATIME_SUFFIX
        orig_put = _lru.LRUCache.put
        orig_get = _lru.LRUCache.get
    except (ImportError, AttributeError):
        return
    if getattr(_lru.LRUCache.put, "_ray_tpu_atomic", False):
        return  # already patched in this process

    import time as _time

    def _guarded_get(self, key):
        if key and _blocked_key(key):
            return None
        return orig_get(self, key)

    def _atomic_put(self, key, val):
        if not key:
            raise ValueError("key cannot be empty")
        if _blocked_key(key):
            return
        if self.eviction_enabled and len(val) > self.max_size:
            return orig_put(self, key, val)   # upstream warns + drops
        cache_path = self.path / f"{key}{cache_suffix}"
        atime_path = self.path / f"{key}{atime_suffix}"
        if self.eviction_enabled:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if cache_path.exists():
                return
            self._evict_if_needed(additional_size=len(val))
            # Same dir => same filesystem => rename is atomic. A stray
            # .tmp from a kill-mid-write never matches the cache suffix,
            # so it can only waste bytes, not poison a read.
            tmp = self.path / f"{key}.{_os.getpid()}.tmp"
            tmp.write_bytes(val)
            _os.replace(tmp, cache_path)
            if self.eviction_enabled:
                atime_path.write_bytes(
                    _time.time_ns().to_bytes(8, "little"))
        finally:
            if self.eviction_enabled:
                self.lock.release()

    _atomic_put._ray_tpu_atomic = True
    _lru.LRUCache.put = _atomic_put
    _lru.LRUCache.get = _guarded_get

    # Sweep tmp debris from previously killed writers (>1h old: never a
    # live writer's pending rename).
    cache_dir = _os.environ.get(_CACHE_ENV)
    if cache_dir and _os.path.isdir(cache_dir):
        now = _time.time()
        for fn in _os.listdir(cache_dir):
            if fn.endswith(".tmp"):
                p = _os.path.join(cache_dir, fn)
                try:
                    if now - _os.path.getmtime(p) > 3600:
                        _os.unlink(p)
                except OSError:
                    pass


def harden_jax_compilation_cache_on_import() -> None:
    """Arrange for ``harden_jax_compilation_cache`` to run the moment jax's
    cache module is first imported, WITHOUT importing jax now.

    Worker processes need the cache patch (they compile and read entries
    via the env-inherited JAX_COMPILATION_CACHE_DIR) but must not import
    jax at bootstrap — that adds seconds to every worker start and
    measurably slows the whole cluster suite. A task-boundary check
    can't close the gap either: a worker whose single long task imports
    jax and compiles would write/read entries before any later boundary.
    A one-shot import hook fires exactly when ``jax._src.lru_cache``
    finishes executing — before any cache get/put can possibly happen.

    If jax (and its cache module) is somehow already imported, the patch
    is applied immediately instead."""
    import importlib.util
    import sys as _sys

    target = "jax._src.lru_cache"
    if target in _sys.modules:
        harden_jax_compilation_cache()
        return
    if any(getattr(f, "_ray_tpu_harden_hook", False)
           for f in _sys.meta_path):
        return

    class _WrapLoader:
        def __init__(self, inner):
            self._inner = inner

        def create_module(self, spec):
            return self._inner.create_module(spec)

        def exec_module(self, module):
            self._inner.exec_module(module)
            # Module is fully executed and present in sys.modules here
            # (the import system sets the parent attribute only after
            # exec returns; harden's `from jax._src import lru_cache`
            # falls back to sys.modules, so this is safe mid-import).
            harden_jax_compilation_cache()

        def __getattr__(self, name):
            return getattr(self._inner, name)

    class _Finder:
        _ray_tpu_harden_hook = True

        def find_spec(self, fullname, path, target_mod=None):
            if fullname != target:
                return None
            _sys.meta_path.remove(self)        # one-shot
            spec = importlib.util.find_spec(fullname)
            if spec is None or spec.loader is None:
                return None
            spec.loader = _WrapLoader(spec.loader)
            return spec

    _sys.meta_path.insert(0, _Finder())
