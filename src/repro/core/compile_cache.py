"""Where compiled programs and the batch-size autotune result persist.

JAX's persistent compilation cache is keyed by, among other things, the
cache directory, so a directory that moves between runs never hits. The
cache therefore lives where `JAX_COMPILATION_CACHE_DIR` says when that is
set (JAX reads it itself), and otherwise at the fixed `.jax_cache/` at
the repository root, a build output listed in `.gitignore`.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
JAX_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR
