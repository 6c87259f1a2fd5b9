"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` by itself; where it is set this
module does nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the directory is part of what a later process must find
again (never a temporary name, a pid or a time).

The cache is keyed on the programs' op metadata too.  JAX leaves it out of
the key by default, and the metadata carries the model's layer scopes
(``jax.named_scope``) that a profiler trace attributes device time by: a
program compiled from other source would otherwise be read back in place
of this one, and its trace would show none of this source's scopes.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring); returns that directory.  Call before the first
    compile."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
