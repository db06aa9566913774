"""Persistent XLA compilation cache shared by every launcher and
``chip_smoke.py``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and no
other directory is set. Otherwise it lives at one fixed path inside the
checkout, ``<repo>/.jax_cache``: a directory that moved between runs
would never hit. Thresholds are zeroed so even sub-second programs are
cached (the default 1s floor would skip everything the reduced configs
compile), so a second process launch of the same program deserializes
executables instead of recompiling.

The key includes the program's metadata: the op names that
``jax.named_scope`` gives (``vr/local_epoch``, ...) live there, and with
the default key, which strips it, an executable cached from code with
other names (or none) is reused, and a profile shows those names.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cache_dir() -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed checkout path."""
    return os.path.abspath(os.environ.get(ENV_VAR) or DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`cache_dir` and return it.
    Must run before the first compilation; safe to call more than once."""
    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
