"""redisson_tpu — a TPU-native in-memory data grid.

Brand-new framework with the capabilities of the reference Java/Redis client
(`lysdtbu/redisson`, see SURVEY.md): rich distributed objects, synchronizers,
and distributed services — with the data plane executed on TPU via JAX/XLA
(sketch/bit/register state as sharded device tensors, compound ops as fused
kernels dispatched per micro-batch) instead of a Redis server.

Layering (SURVEY.md §7.1):
  ops/       L1' pure state kernels (BitTensor, HllTensor, ...)
  core/      L2' execution engine (store, per-shard sequencer, micro-batching)
  parallel/  L3' mesh/slot topology, sharded kernels, collectives
  server/    L4' RESP-style asyncio protocol server + client
  client/    L5'/L6' object handles + Redisson-style entry facade
  services/  L6' executor, MapReduce, remote service, transactions
  utils/     hashing, crc16, timers, misc
"""
import os
import threading
from typing import Dict, Optional

from redisson_tpu.version import __version__  # noqa: F401

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_cache_lock = threading.Lock()
_cache_configured = False
_cache_events = {"hits": 0, "writes": 0, "programs": 0, "compile_s": 0.0}


def compile_cache_dir() -> Optional[str]:
    """Where this process keeps its persistent XLA compile cache — the ONE
    place that decides it (jax-free: a parent that must stay off the chip
    can ask too).  ``JAX_COMPILATION_CACHE_DIR`` wins on every platform:
    JAX reads it itself and no directory is set in code.  Otherwise the
    cache lives at ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what a later process must find again — except on
    hermetic CPU runs (``JAX_PLATFORMS=cpu``: tests, dry runs), which keep
    no cache: XLA:CPU entries pin the host's machine features, and a
    checkout copied to another machine would carry them along."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point JAX at ``compile_cache_dir()`` so a fresh process (server boot,
    WorkerNode spawn, bench child) reloads prior TPU compiles instead of
    re-lowering them.  Called lazily from Engine.__init__ — NOT at package
    import: wire-only clients never touch jax.  Safe before backend init:
    jax.config updates don't initialize a backend.  Returns the directory
    in use (None = no cache)."""
    global _cache_configured

    cache_dir = compile_cache_dir()
    with _cache_lock:
        if _cache_configured:
            return cache_dir
        _cache_configured = True
    import jax

    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    if cache_dir is None:
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # caching sub-0.1s programs costs more in serialize/write overhead than
    # the recompiles do
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return cache_dir


def _on_jax_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["writes"] += 1


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _cache_events["programs"] += 1
        _cache_events["compile_s"] += duration_secs


def compile_cache_stats() -> Dict[str, float]:
    """What compiling cost THIS process since enable_compile_cache — a
    set-up fact, never a rate.  ``programs`` / ``compile_s``: XLA programs
    built or loaded and the seconds that took (jax's backend-compile event
    covers both); ``hits`` loaded a stored executable from the persistent
    cache, ``writes`` compiled one and stored it (jax's ``cache_misses``
    event fires exactly at the write)."""
    return dict(_cache_events)


def create(config=None):
    """Create an embedded-mode client (Redisson.create analog)."""
    from redisson_tpu.client.redisson import RedissonTpu

    return RedissonTpu.create(config)


__all__ = [
    "__version__", "compile_cache_dir", "compile_cache_stats", "create",
    "enable_compile_cache",
]
