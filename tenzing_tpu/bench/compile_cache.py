"""Persistent XLA compilation cache shared by the bench drivers.

The search wall is dominated by compiles (~3.4 s per distinct schedule — the
counter report in the driver tail), and repeat/confirm driver invocations
re-trace identical schedules; cache hits turn those into milliseconds, so the
same wall budget buys more search.  Measured times are unaffected (the cache
only skips the XLA compile step).

One rule, in this one function, for where the cache lives — the path is part
of the cache key, so a directory that moves never hits:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
  set in code, and child processes (drain children, fleet workers) inherit
  it through the environment.
* unset: one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored) — the same for every process started from this tree.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# tenzing_tpu/bench/compile_cache.py -> the checkout root
IN_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on and return its directory
    (see the module docstring for which)."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = IN_CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return path
