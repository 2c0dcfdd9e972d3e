"""Where JAX's persistent compilation cache lives — the one rule, in one
place.

The directory is part of every cache key, so a path that moves never
hits: it is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself, and nothing here names another
directory), and otherwise the fixed ``<checkout>/.jax_cache`` — no temp
name, pid or timestamp.  ``chip_smoke.py``, ``bench.py``, the benchmarks
and the two CLIs (``models.cli``, ``serving.server``) all call
:func:`enable_compile_cache`, before their first compile: JAX decides
once per process whether the cache is in use.  The same call makes the
process's compiles program events (``telemetry.xla_introspect``:
``xla.backend_compiles``, ``xla.persistent_cache_hits``,
``xla.backend_compile_seconds`` and an ``xla.backend_compile`` span record
each).
"""

from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The environment's directory when set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`; returns
    the directory.  With the variable set JAX has already pointed itself
    there, so the directory is left alone."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's default keeps only programs that took over a second to compile;
    # this system's are many and small (chip run, PR 22: 84 programs, 17 s
    # in all, nearly every one under the bar), so keep every one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # JAX's default key leaves out op metadata, so a cached executable keeps
    # the ``op_name`` and source lines of whoever compiled it first: a
    # profile then shows scope names the program no longer has (chip run,
    # PR 28: the new ``jax.named_scope`` names were absent from the trace
    # until the key took them in).  The price is a recompile after an edit
    # that only moves lines.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from ..telemetry.xla_introspect import install_compile_listeners
    install_compile_listeners()
    return path
