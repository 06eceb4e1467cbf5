"""Persistent XLA compilation cache — the cold-start lever.

Reference analog: none to port — upstream serving pays full compile (or
torch load) on every pod start; BASELINE config 5 measures that cost as
``cold_start_s``. XLA compiles are pure functions of (HLO, flags,
backend), so JAX's persistent compilation cache turns every process
start after the first into a disk read. Every long-lived entrypoint
(ModelServer, LMEngine, Trainer, the CLI, bench) calls
:func:`enable_compilation_cache` at construction; it is idempotent and
can be opted out of with ``KFT_NO_COMPILATION_CACHE=1`` (e.g. hermetic
CI).

The directory is placed from outside, one way: ``JAX_COMPILATION_CACHE_DIR``
(JAX's own variable, read by JAX at import). Unset, the cache lives at a
fixed path inside the checkout — the path is part of the cache key, so a
directory derived from home, a temp name, a pid or the time never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

from kubeflow_tpu.obs import names, prom

#: ``<checkout>/.jax_cache`` (git-ignored), resolved from this file.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: What start-up costs, in this process: fed by JAX's own monitoring
#: events, exposed on every ModelServer's ``/metrics``.
XLA_PROGRAMS = prom.REGISTRY.counter(
    names.XLA_PROGRAMS_TOTAL,
    "XLA programs built (compiled or loaded from the persistent cache)",
)
XLA_COMPILE_SECONDS = prom.REGISTRY.counter(
    names.XLA_COMPILE_SECONDS_TOTAL,
    "wall seconds spent building XLA programs, cache loads included",
)
XLA_CACHE_HITS = prom.REGISTRY.counter(
    names.XLA_CACHE_HITS_TOTAL,
    "XLA programs supplied by the persistent compilation cache",
)
_listening = False


def _count_compiles() -> None:
    """Subscribe the counters above to JAX's compile events (once)."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            XLA_PROGRAMS.inc()
            XLA_COMPILE_SECONDS.inc(seconds)

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            XLA_CACHE_HITS.inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def compile_stats() -> dict[str, float]:
    """Snapshot of the counters: programs, seconds, cache_hits."""
    return {
        "programs": XLA_PROGRAMS.labels().value,
        "seconds": XLA_COMPILE_SECONDS.labels().value,
        "cache_hits": XLA_CACHE_HITS.labels().value,
    }


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache.

    Returns the active cache dir, or None when ``KFT_NO_COMPILATION_CACHE``
    is set. A directory already configured — ``JAX_COMPILATION_CACHE_DIR``
    or an earlier call — is kept and none is set in code; otherwise
    :data:`DEFAULT_CACHE_DIR` is created (an ``OSError`` propagates: a
    start-up that silently compiled everything again would hide the cost).
    """
    _count_compiles()
    if os.environ.get("KFT_NO_COMPILATION_CACHE"):
        return None
    import jax

    # serving buckets are small programs that still take seconds of XLA
    # time on TPU; the default 1s floor would skip exactly the programs a
    # cold start pays for. Lowered even when the dir came from
    # JAX_COMPILATION_CACHE_DIR — an "enabled" cache that never persists
    # the serving programs would be a lie.
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    if floor is None or floor > 0.2:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    DEFAULT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
