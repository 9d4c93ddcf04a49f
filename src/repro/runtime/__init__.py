"""Runtime support for processes that run JAX on the chip.

:func:`use_compile_cache` places JAX's persistent compilation cache.  A
run finds only what earlier runs wrote to the same directory, so the
path is fixed, never temporary: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, otherwise ``<checkout>/.jax_cache``.

:func:`annotate_spans` puts ``repro.obs`` spans into the profiler's
trace while one runs.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "annotate_spans", "use_compile_cache"]

# src/repro/runtime/__init__.py → the checkout holding src/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into ``jax_compilation_cache_dir`` and nothing is changed.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def annotate_spans() -> None:
    """Install ``jax.profiler.TraceAnnotation`` as ``repro.obs``'s
    profiler bridge: while a profiler runs, every span is also an
    annotation in its trace (``repro.obs.core``)."""
    import jax

    from .. import obs

    obs.set_annotator(jax.profiler.TraceAnnotation)
