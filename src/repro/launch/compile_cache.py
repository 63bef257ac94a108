"""JAX's persistent compilation cache, placed once for every entry point.

A cold process compiles each of the solver's stage programs from
scratch (seven for the default two SRS rounds), which on a TPU takes
minutes at chip-filling sizes. The persistent cache keeps those
executables across processes in one directory, which therefore must not
move between runs.

JAX evicts the least recently used entries once the directory outgrows
``jax_compilation_cache_max_size`` (``JAX_COMPILATION_CACHE_MAX_SIZE``).
The executables are large: the default config's stage programs at 2^24
elements take 178 MiB on a v5e, so a bound below what one run compiles
keeps little for the next.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed in-checkout default (listed in .gitignore).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the cache on for an accelerator backend; return its
    directory, or None on the CPU backend.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory and JAX
    reads it itself; otherwise the cache lives at :data:`DEFAULT_DIR`.
    Every program is written, however short its compile (JAX's default
    keeps only compiles over one second). The CPU backend is left alone:
    its compiles are short, and each cached CPU executable it reads back
    logs a host-feature mismatch error.
    """
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
