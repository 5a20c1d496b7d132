"""Persistent XLA compilation cache — compile once per program, per machine.

Every fresh process re-pays the full XLA compile.  JAX's persistent
compilation cache keyed on (HLO, compile options, backend) removes that
for any repeated program — the repeat-compile cases that are everywhere in
a pipeline framework: re-running a pipeline after editing one node,
subprocess-isolated Tuner trials (each trial process compiles the same
model), serving restarts, and retries.  How much a warm cache saves on the
chip is in PERF.md.

Where the caches live (one root for all three — XLA executables here, the
serving AOT executables of ``serving/aot.py`` under ``<root>/aot``, the
flash-attention autotune tables of ``ops/autotune.py`` under
``<root>/autotune``):

  JAX_COMPILATION_CACHE_DIR=<dir>  JAX's own variable.  When set, JAX has
                                   already read it into
                                   ``jax_compilation_cache_dir``; this module
                                   sets no directory in code and ``<dir>`` is
                                   the root.
  (unset)                          ``<checkout>/.cache`` — a fixed path inside
                                   the checkout (git-ignored), XLA entries
                                   under ``.cache/xla``.  Fixed because the
                                   path is part of the cache key: a directory
                                   that moves never hits.

  TPP_COMPILE_CACHE=0              disable the XLA cache entirely

Only compiles slower than 1 s are persisted, so µs-scale CPU test jits
don't churn the cache.  Callers invoke :func:`maybe_enable_compile_cache`
at process entry (runner construction, cluster-pod entrypoint, tuner
trial, serving startup, train_loop) — idempotent, and a failure to set up
the cache degrades to uncached compiles, never an error.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)

ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_STATE = {"configured": False, "enabled": False}


def cache_root() -> str:
    """The one directory every on-disk cache of this package lives under."""
    return os.environ.get(ENV_JAX_CACHE_DIR, "").strip() or os.path.join(
        _CHECKOUT, ".cache"
    )


def maybe_enable_compile_cache() -> bool:
    """Idempotently point JAX at the persistent compilation cache.

    Returns True when the cache is active.  Must run before the first
    compile to benefit that compile; safe (and cheap) to call any time.
    """
    if _STATE["configured"]:
        return _STATE["enabled"]
    _STATE["configured"] = True
    if os.environ.get("TPP_COMPILE_CACHE", "1") == "0":
        return False
    try:
        import jax

        if jax.config.jax_compilation_cache_dir:
            # Set from outside (JAX_COMPILATION_CACHE_DIR, or jax.config
            # by the embedding program) — respect it, never repoint it.
            _STATE["enabled"] = True
            return True
        cache_dir = os.path.join(cache_root(), "xla")
        os.makedirs(cache_dir, exist_ok=True)
        # Filter BEFORE activating the dir: if this knob is missing on a
        # jax version, we fail closed (no cache) rather than activating an
        # unfiltered cache that micro-jits would churn.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    except Exception as e:  # noqa: BLE001 — cache is an optimization only
        log.warning("persistent compile cache unavailable: %s", e)
        return False
    _STATE["enabled"] = True
    log.debug("persistent XLA compile cache at %s", cache_dir)
    return True
