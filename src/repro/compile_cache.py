"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at start-up; library code never does, so
importing :mod:`repro` changes no JAX configuration.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

__all__ = ["source_path_regex", "use_compile_cache"]


def source_path_regex(root) -> str:
    """The regex that strips the checkout ``root`` from source paths.

    A Pallas kernel's compiled payload records its source file, and the
    persistent cache keys on that payload: without this, two checkouts of
    the same commit at different paths never share a compiled program.
    """
    return "^" + re.escape(str(Path(root).resolve())) + "/"


def use_compile_cache(root) -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed. Otherwise the cache goes to ``<root>/.jax_cache``:
    a path fixed by the checkout, never by a temp name, a process id or the
    time, so a later run from the same checkout finds its entries. Either
    way source paths are recorded relative to ``root``.
    """
    import jax

    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      source_path_regex(root))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
