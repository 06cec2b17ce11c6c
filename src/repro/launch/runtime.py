"""Process runtime shared by every entry point: the persistent compile
cache, compile accounting, and the device a run actually used.

``use_compile_cache()`` must run before jax is imported: jax reads
``JAX_COMPILATION_CACHE_DIR`` when it loads.  It keeps a directory the
environment already names, and otherwise names ``.jax_cache/`` at the
root of this checkout.  The path is fixed — never a temporary name, a
pid or a time — because a cache only hits where the same path is read
again, and every child (campaign attempts, gang ranks) inherits it
through the environment.

The other two helpers import jax and belong to processes that run a
model: ``compile_stats()`` counts compile seconds (persistent-cache
reads included) and cache hits and writes from jax's monitoring events
while a block runs, and ``device_report()`` names the device a run
used.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Any, Dict, Iterator

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/runtime.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"   # emitted per write
_COMPILE_TIME = "/jax/core/compile/backend_compile_duration"


def use_compile_cache() -> str:
    """Point jax's persistent compile cache at its fixed directory (see
    the module docstring) and return that directory."""
    path = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    os.environ[CACHE_ENV] = path
    return path


@contextlib.contextmanager
def compile_stats() -> Iterator[Dict[str, Any]]:
    """Yield a dict that counts, while the block runs, this process's
    backend compile seconds (a persistent-cache read counts as its
    compile), cache hits and cache writes."""
    import jax

    stats: Dict[str, Any] = {
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_s": 0.0, "cache_hits": 0, "cache_writes": 0}

    def on_event(event: str, **_):
        if event == _CACHE_HIT:
            stats["cache_hits"] += 1
        elif event == _CACHE_WRITE:
            stats["cache_writes"] += 1

    def on_duration(event: str, secs: float, **_):
        if event == _COMPILE_TIME:
            stats["compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield stats
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)


def device_report() -> Dict[str, Any]:
    """The devices this process ran on, as jax reports them, with the
    first local device's peak memory where the backend tracks it."""
    import jax

    devices, local = jax.devices(), jax.local_devices()
    report: Dict[str, Any] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "local_ids": [d.id for d in local]}
    peak = (local[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        report["peak_bytes_in_use"] = int(peak)
    return report
