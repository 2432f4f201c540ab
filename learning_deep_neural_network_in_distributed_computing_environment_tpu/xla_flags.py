"""Process-level XLA flag setup that must run BEFORE jax initializes a
backend.  Import-light on purpose (os only): callers import this before
any jax import can win the race.
"""

from __future__ import annotations

import os

SEQUENTIAL_CPU_COLLECTIVES_FLAG = (
    "--xla_cpu_enable_concurrency_optimized_scheduler=false")


def ensure_sequential_cpu_collectives() -> bool:
    """Pin the sequential CPU thunk scheduler via XLA_FLAGS.

    The concurrency-optimized XLA:CPU thunk executor may enter
    DAG-independent collectives in a nondeterministic per-device order;
    with intersecting device groups (e.g. a seq-pair psum racing a pipe
    ppermute under SP x PP) two virtual devices can join different
    rendezvous and deadlock — 40 s timeout, then SIGABRT.  The sequential
    scheduler gives every virtual device the same collective order.
    Real-TPU runs are unaffected (collectives execute in stream order).

    Returns True when the flag is (now) present.  Only effective if the
    CPU backend has not been initialized yet — callers run this at import
    time, before jax.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " " + SEQUENTIAL_CPU_COLLECTIVES_FLAG).strip()
    return True


# --- the persistent compile cache: ONE rule ----------------------------
# A cache that moves never hits, so the directory is fixed: it is
# ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets one (JAX
# reads that variable itself at import; this module then sets nothing),
# and otherwise ``<checkout>/.jax_cache`` — absolute, derived from this
# file, never the working directory, a temp name, a pid or a time.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Where this process keeps its persistent XLA compilation cache."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_COMPILE_CACHE_DIR


def setup_compile_cache() -> str:
    """Arm JAX's persistent compilation cache at ``compile_cache_dir()``
    and the hit/miss counter (``compile_cache_counts``); returns the
    directory.  The only code site that sets
    ``jax_compilation_cache_dir``, and it does not when the environment
    variable is set.  Safe to call mid-process and more than once: jax
    0.9 initializes the cache object at the first compile that finds a
    directory configured, and the directory never changes after that.
    Imports jax lazily so this module stays importable before backend
    init."""
    import jax
    path = compile_cache_dir()
    if os.environ.get(CACHE_DIR_ENV):
        if jax.config.jax_compilation_cache_dir != path:
            raise RuntimeError(
                f"{CACHE_DIR_ENV}={path!r} was set after jax was "
                "imported, so jax never read it (its cache directory is "
                f"{jax.config.jax_compilation_cache_dir!r}); export it "
                "before the process starts")
    else:
        jax.config.update("jax_compilation_cache_dir", path)
    install_cache_counter()
    return path


# --- persistent-cache hit/miss telemetry (ROADMAP open item) ---------------
# JAX's compilation cache emits monitoring events on every lookup; the
# listener below turns them into process-level counters a run can snapshot
# before/after (driver.train_global reports the delta per run).

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_cache_counts = {"hits": 0, "misses": 0}
_cache_counter_installed = False


def install_cache_counter() -> None:
    """Register a jax monitoring listener counting persistent-cache hits
    and misses.  Idempotent."""
    global _cache_counter_installed
    if _cache_counter_installed:
        return
    from jax._src import monitoring

    def _listen(event, **kwargs):
        if event == _CACHE_HIT_EVENT:
            _cache_counts["hits"] += 1
        elif event == _CACHE_MISS_EVENT:
            _cache_counts["misses"] += 1

    monitoring.register_event_listener(_listen)
    _cache_counter_installed = True


def compile_cache_counts() -> dict:
    """Cumulative persistent-cache {hits, misses} for this process."""
    return dict(_cache_counts)


# --- trace/compile event telemetry (ISSUE 6 runtime sanitizer) -------------
# Unlike the persistent-cache hit/miss counters above (which only fire when
# the compilation cache is armed), jax emits trace/compile DURATION events on
# every jaxpr trace and every backend compile, cache or no cache — exactly
# the signal the sanitizer's per-round retrace budget needs: after the
# warmup round, a healthy round loop performs ZERO new traces.

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_event_counts = {"traces": 0, "compiles": 0}
_compile_counter_installed = False


def install_compile_counter() -> None:
    """Register a listener counting jaxpr traces and backend compiles.
    Idempotent."""
    global _compile_counter_installed
    if _compile_counter_installed:
        return
    from jax._src import monitoring

    def _listen(event, duration, **kwargs):
        if event == _TRACE_EVENT:
            _compile_event_counts["traces"] += 1
        elif event == _BACKEND_COMPILE_EVENT:
            _compile_event_counts["compiles"] += 1

    monitoring.register_event_duration_secs_listener(_listen)
    _compile_counter_installed = True


def compile_event_counts() -> dict:
    """Cumulative {traces, compiles} for this process (zeros until
    ``install_compile_counter`` succeeds)."""
    return dict(_compile_event_counts)


def sequential_cpu_collectives_pinned() -> bool:
    """Whether XLA_FLAGS pins the SEQUENTIAL scheduler — used by the
    driver to fail fast instead of deadlocking when a hazardous
    composition is requested on an unpinned CPU backend.

    Only ``...concurrency_optimized_scheduler=false`` counts as pinned:
    an explicit ``=true`` selects the deadlock-prone scheduler, which is
    exactly the hazardous configuration (advisor r3 — the old
    substring-presence check was bypassed by it)."""
    for flag in os.environ.get("XLA_FLAGS", "").split():
        if "xla_cpu_enable_concurrency_optimized_scheduler" in flag:
            _, _, value = flag.partition("=")
            # TSL bool flag parsing also accepts 0/1 spellings
            return value.strip().lower() in ("false", "0")
    return False
