"""Persistent XLA compilation cache placement.

The engine dispatches a handful of big executables (the packed repair
wave per pod schema, the blocked and exact scan lanes per capacity tier),
each keyed on a small set of static table capacities — exactly the shape
the JAX persistent cache is built for.  The reference has no analog (Go
compiles ahead of time); for a jit-traced framework the cache IS the AOT
story: a warm boot loads every program instead of compiling it.

Placement — the cache directory is part of every entry's key, so it must
not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets NO directory in code — whoever runs the process places the cache.
* unset: ``<checkout>/.jax_cache`` (git-ignored).

:func:`enable_persistent_cache` runs before the first compilation:
``__main__.start`` calls it whenever the device engine is on, and the
bench children, the profile scripts and the test conftest call it
themselves.  ``MINISCHED_CACHE=0`` skips it (JAX then caches only if the
variable above is set, with its own default thresholds).
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str | None:
    """Turn JAX's persistent compilation cache on for every compilation
    that follows; returns the directory in effect (None when skipped via
    ``MINISCHED_CACHE=0``).  Idempotent, and safe after jax is imported —
    the config flags are read at each compile."""
    if os.environ.get("MINISCHED_CACHE", "1") == "0":
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    # cache everything: besides the big programs every boot re-runs dozens
    # of small ones (static-classification probes, table splitters, the
    # static-column transfers), and JAX's default thresholds would compile
    # each of those again — a warm boot should compile nothing
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # keep the jax-level executable cache but NOT XLA's own AOT kernel
    # caches: XLA:CPU AOT loads hard-check machine features — including
    # XLA pseudo-features host detection never reports — so every load
    # warns about a mismatch and is documented as able to SIGILL
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    return jax.config.jax_compilation_cache_dir
