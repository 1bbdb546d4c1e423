"""Where JAX keeps its persistent compilation cache for this repository's
entry points (``chip_smoke.py``, ``repro.launch.serve``).

A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own to read, and nothing here
overrides it.  Otherwise the cache goes to one fixed directory inside the
source checkout: the directory is part of each entry's key, so a path that
moved between runs (a temporary name, a pid, a timestamp) would never hit.
Tests never call this — they run with the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Optional[Path]:
    """The directory to set, or None when the environment already names one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return CHECKOUT_CACHE_DIR


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
