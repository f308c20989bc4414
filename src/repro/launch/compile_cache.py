"""Persistent XLA compilation cache, placed from outside the program.

A cold chip run compiles a 2.5 B-parameter training step and the serving
programs; the persistent cache lets the next process on the same machine
load them instead.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and the
program sets nothing (JAX reads the variable itself).  Otherwise the cache
lives at ``<repo>/.jax_cache`` — a fixed path, because the path is part of
every entry's key: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ=os.environ) -> Path | None:
    """The directory this program must set, or None when the environment
    already names one."""
    return None if environ.get(ENV) else REPO_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.  Called
    by the launchers' ``main`` and by ``chip_smoke.py`` — never at import,
    so library users and the tests keep JAX's own default."""
    path = cache_dir()
    if path is None:
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
