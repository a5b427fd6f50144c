"""Where compiled programs persist between runs.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set, nothing
here overrides it. Otherwise the cache goes to `<repo>/.jax_cache`: a
fixed path, because the path is part of each entry's key, so a
directory that moved between runs would never be hit.
"""
import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its
    directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
