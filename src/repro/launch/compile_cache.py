"""JAX's persistent compilation cache, placed from outside.

A compiled chip program is found again only under the same cache path, so
the path is fixed: `JAX_COMPILATION_CACHE_DIR` when the environment sets
it, and otherwise `<repo>/.jax_cache` (listed in `.gitignore`). Entry
points (`mce_run.main`, `mce_service.main`, `chip_smoke.py`) call
`enable()` once before their first compile; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """The environment's directory if set, else the fixed repo-root one."""
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()`."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
