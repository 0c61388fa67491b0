"""Persistent XLA compilation-cache setup, shared by the server entry
point and the offline tools (bulk builds, benchmarks).

The vector store's pow2 capacity ladder and the bulk-build link pipeline
re-jit per shape level; each program costs 0.5-20 s to compile. Two
defaults make every process after the first start warm:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
  this module sets no directory; otherwise the cache lives at ONE fixed
  path inside the checkout, ``<checkout>/.cache/jax`` (git-ignored) —
  the path is part of the cache key, so a directory that moves with the
  user, a temp name, a pid or a time would never hit
- persistence threshold 0: jax's default skips sub-1 s compiles, which
  is exactly the population the capacity ladder is made of
"""

from __future__ import annotations

import logging
import os
import threading

logger = logging.getLogger(__name__)

_done = False
_metrics_installed = False

# executable-footprint estimate: XLA keeps compiled programs resident in
# HBM but exposes no per-executable size; each backend compile bumps one
# ledger entry by a flat estimate (HBM_EXECUTABLE_ESTIMATE_BYTES,
# default 4 MiB) so the allocator-vs-ledger delta in /v1/debug/memory
# isn't silently dominated by executables. Explicitly labeled
# sharding="estimate" — this is a planning number, not an exact count.
_exec_key: int | None = None
_exec_count = 0
_exec_lock = threading.Lock()


def _note_executable() -> None:
    """Called from jax's monitoring callbacks, which fire on whatever
    thread finished the compile — the lock keeps concurrent first
    compiles from double-registering (and orphaning) ledger entries."""
    global _exec_key, _exec_count
    try:
        from weaviate_tpu.runtime.hbm_ledger import ledger

        est_each = int(os.environ.get("HBM_EXECUTABLE_ESTIMATE_BYTES",
                                      str(4 << 20)))
        with _exec_lock:
            _exec_count += 1
            if _exec_key is None:
                _exec_key = ledger.register(
                    "executables", est_each * _exec_count,
                    collection="_runtime", shard="-", tenant="",
                    sharding="estimate")
            else:
                ledger.update(_exec_key, est_each * _exec_count)
    except Exception:  # noqa: BLE001 — accounting is best-effort
        pass


def install_compile_metrics() -> None:
    """Feed compile-time histograms and cache hit/miss counters from
    jax's monitoring stream (idempotent; safe without jax).

    jax emits ``record_event_duration_secs`` for every backend compile
    ('/jax/core/compile/backend_compile_duration' and friends) and
    ``record_event`` for persistent-cache outcomes ('/jax/compilation_
    cache/cache_hits' | 'cache_misses' | 'task_disabled_cache'). The
    event key IS the signature label — keys are a small fixed set, so
    cardinality stays bounded while still splitting tracing/lowering/
    backend-compile time."""
    global _metrics_installed
    if _metrics_installed:
        return
    _metrics_installed = True
    try:
        from jax import monitoring

        from weaviate_tpu.runtime.metrics import (compile_cache_events,
                                                  jit_compile_duration)

        def _on_duration(event: str, duration: float, **kw) -> None:
            if "compile" in event:
                jit_compile_duration.labels(event).observe(duration)
                if "backend_compile" in event:
                    _note_executable()

        def _on_event(event: str, **kw) -> None:
            if "cache_hit" in event:
                compile_cache_events.labels("hit").inc()
            elif "cache_miss" in event:
                compile_cache_events.labels("miss").inc()
            elif "compilation_cache" in event:
                compile_cache_events.labels("other").inc()

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception as e:  # noqa: BLE001 — metrics are best-effort
        logger.debug("compile metrics unavailable: %s", e)


#: the one in-code cache location: derived from the package's own place
#: on disk, identical for every process started from this checkout
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")


def cache_dir() -> str | None:
    """Directory the persistent cache writes to (None: not persisting)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def ensure_compile_cache() -> None:
    """Idempotent; call before the first jit dispatch. A cache that
    cannot be set up is an error — a silently cold server recompiles its
    whole ladder on every start."""
    global _done
    if _done:
        return
    _done = True
    install_compile_metrics()
    import jax

    explicit = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not explicit and jax.default_backend() == "cpu":
        # tier-1 runs six CPU workers at once and must not write a cache
        # (nor read one another run left behind); CPU compiles are cheap.
        # Cache only accelerator programs unless the variable opts in.
        return
    if not explicit:
        os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
