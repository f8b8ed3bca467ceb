"""Persistent XLA compile cache at a fixed place.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set the
cache stays where it says.  Otherwise ``enable_compile_cache`` puts it in
``<checkout>/.jax_cache/``, a path fixed by this file's own location: a
cache directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compile cache; returns its directory.

    Returns None (cache left off) when this module was installed outside
    a checkout, where no fixed repo-local directory exists.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (CHECKOUT / "pyproject.toml").is_file():
        return None
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
