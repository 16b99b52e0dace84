"""Where XLA's persistent compile cache lives.

Every chip-tool call starts on a new machine, and the 1.3 B train and
serve programs take tens of seconds each to compile, so the cache has to
be placeable from outside: an operator (or the driver) sets
`JAX_COMPILATION_CACHE_DIR` and jax reads it itself — this module then
sets nothing. Without the variable the cache goes to ONE fixed directory
inside the checkout. The directory is part of the cache key's world: a
path built from a pid, a timestamp or `tempfile` never hits twice.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore); resolved from this file,
# not from the working directory
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Called once at package import, before any compile. Returns the
    directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_dir() -> str:
    """The directory jax is configured with right now."""
    return jax.config.jax_compilation_cache_dir


def entry_count() -> int:
    """Executables in the cache directory (0 when it does not exist
    yet): jax writes one `<name>-<key>-cache` file per executable."""
    try:
        names = os.listdir(cache_dir())
    except FileNotFoundError:
        return 0
    return sum(1 for n in names if n.endswith("-cache"))
