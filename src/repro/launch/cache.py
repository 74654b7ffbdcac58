"""Where JAX's persistent compilation cache lives.

A cached program is found again only under the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), else ``.jax_cache`` at the root of the
checkout (git-ignored).
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; call before the first compile.

    Returns the directory in use.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
