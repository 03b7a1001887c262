"""The one place the persistent XLA compilation cache is configured.

Every entry point that compiles round programs (experiments/run.py,
bench.py, the tools, tests/conftest.py, chip_smoke.py) calls
:func:`enable_compile_cache` before its first compile, so a second process
on the same machine finds the first one's executables instead of paying
the compile again (a cold flagship run is minutes of XLA time; the chip
tool starts every call cold unless the cache directory survives).

The directory is part of the cache key's lookup path, so it must not move:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself at
  import; this module sets NO directory in code (a ``jax.config.update``
  would override the operator's choice).
- not set: ``<checkout>/.jax_cache`` — a fixed path (git-ignored), never a
  temporary name, a pid or a time.
"""

from __future__ import annotations

import os

#: the checkout root (the directory holding ``fedml_tpu/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: executables that took less than this to compile are not worth a file;
#: -1 bytes = no size floor (small round programs still compile for seconds)
MIN_COMPILE_SECS = 0.5
MIN_ENTRY_BYTES = -1


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache and set its thresholds; returns
    the directory in effect. Idempotent, and must run before the process's
    first compile (JAX initialises the cache once, on first use)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      MIN_ENTRY_BYTES)
    # An executable read back from the cache carries the metadata of the
    # process that compiled it: op names (the program's jax.named_scope
    # layer names, which the benchmark's trace reduction reads) and source
    # lines. JAX leaves metadata out of the key by default, so a checkout
    # whose scopes or lines differ would be handed another's names (seen
    # on the chip, PR 24: the parent commit's traced run showed this
    # commit's scopes out of a shared cache). Metadata is part of what is
    # read, so it is part of the key.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir


def count_cache_events() -> dict:
    """Start counting the compiler's ``jax.monitoring`` events; returns the
    live dict the listeners update. ``requests`` / ``hits`` / ``misses``
    count the persistent cache: a *miss* is a compile that was written to
    the cache (it took longer than ``MIN_COMPILE_SECS``), a *hit* one that
    was read back, and *requests* every compile that consulted the cache —
    so a warm second run of the same program reports 0 misses.
    ``trace_secs`` / ``lower_secs`` / ``compile_secs`` sum the host seconds
    JAX spent tracing to jaxprs, lowering to MLIR, and compiling (or reading
    the executable back): together a run's set-up time. Only the last is
    what a warm cache removes."""
    import jax

    counts = {"requests": 0, "hits": 0, "misses": 0,
              "trace_secs": 0.0, "lower_secs": 0.0, "compile_secs": 0.0}
    keys = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "misses"}
    stages = {"/jax/core/compile/jaxpr_trace_duration": "trace_secs",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_secs",
              "/jax/core/compile/backend_compile_duration": "compile_secs"}

    def on_event(event: str, **_):
        key = keys.get(event)
        if key is not None:
            counts[key] += 1

    def on_duration(event: str, duration: float, **_):
        key = stages.get(event)
        if key is not None:
            counts[key] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts
