"""Where compiled programs and tuned kernel plans are kept between runs.

One rule for both caches. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing here overrides it. Otherwise the cache is the
fixed directory ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``): a fixed path, because the path is part of the cache key,
and inside the checkout, so a run reads and writes nothing around it.
The autotuned kernel plans (``kernels/autotune.py``) live in the same
directory.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> Path:
    """The directory holding ``pyproject.toml`` above this package (the
    checkout), or the working directory for an installed package."""
    here = Path(__file__).resolve().parent
    for d in (here, *here.parents):
        if (d / "pyproject.toml").is_file():
            return d
    return Path.cwd()


def cache_dir() -> str:
    return os.environ.get(ENV) or str(checkout_root() / ".jax_cache")


def enable() -> str:
    """Turn JAX's persistent compilation cache on at :func:`cache_dir`.
    Call before the first compile; returns the directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
