"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's multi-node-without-a-cluster trick
(`/root/reference/python/ray/cluster_utils.py:99` — N raylets on one machine):
here, N XLA host devices on one process stand in for N TPU chips so every
sharding/collective path is exercised without a pod.

Platform forcing lives in ray_tpu.utils.platform (shared with
__graft_entry__.py) — it must run before any backend is initialized.
"""

import gc
import os

from ray_tpu.utils.platform import (
    force_cpu_devices,
    harden_jax_compilation_cache,
)

force_cpu_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache: the compile-heavy train/spmd/ring tests
# dominate suite wall time; repeat runs hit the cache instead of recompiling
# (cache key includes program + platform, so it is safe across edits).
# Min compile time 0: the width-bucketed serve engine lowers a LADDER of
# small prefill/verify programs (one per pow-2 table width per config) —
# each compiles in well under 0.5 s, but a cold suite pays hundreds of
# them; persisting everything keeps cold-box tier-1 inside its budget.
# A JAX_COMPILATION_CACHE_DIR set from outside wins; otherwise the tests
# use a fixed directory of their own (not the checkout's .jax_cache, so
# a test run never fills the cache chip runs read).
_cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
              or os.environ.get("RAY_TPU_TEST_JAX_CACHE",
                                "/tmp/ray_tpu_jax_cache"))
os.makedirs(_cache_dir, exist_ok=True)
jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
# Subprocesses (workers, multi-process train backends) inherit via env.
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


# A run hard-killed mid-cache-write (the tier runner's timeout SIGKILL,
# an XLA CHECK-failure abort) can tear a `-cache` entry that later
# deserializes into heap corruption — see harden_jax_compilation_cache.
# Workers apply the same patch in their own processes (worker.py main).
harden_jax_compilation_cache()
# Machine-persistent pip runtime-env cache: the venv-build test costs ~60s
# per fresh session dir; content-addressed digests make reuse safe.
os.environ.setdefault("RAY_TPU_PIP_ENV_CACHE_DIR",
                      "/tmp/ray_tpu_pip_env_cache")


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop a test module's compiled programs when it ends. Every loaded
    XLA:CPU executable holds memory mappings until its jit cache entry
    goes, a process may hold `vm.max_map_count` of them (65,530), and one
    xdist worker runs many files: tests/test_laguna.py leaves 39,000
    mappings, test_zaya.py 17,000, test_quant.py 12,000, and a worker
    that was dealt all three died in jaxlib (a segfault or an abort while
    it read or wrote a cache entry) at whatever test crossed the line.
    After `clear_caches` a worker is back under 1,000; the next module
    loads what it needs from the persistent cache."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs
