"""JAX's persistent compilation cache for the program's entry points.

`enable_compile_cache()` is called from the `main()` of
`python -m repro.server`, `python -m repro.launch.serve` and
`chip_smoke.py`, never at import time, so importing `repro` (as the tests
do) sets no cache and writes none.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at `<checkout>/.jax_cache`
(listed in `.gitignore`). The directory is part of every cache key, so it
is a fixed path found from this file, never one built from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(environ: Mapping[str, str] = os.environ) -> str:
    """Turn the persistent cache on for this process; returns its path."""
    if environ.get(ENV_VAR):
        return environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["enable_compile_cache"]
