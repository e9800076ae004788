"""Placement of JAX's persistent compilation cache (XLA executables).

One rule for every entry point that compiles (``python -m
gatekeeper_tpu``, the fleet runner, ``gator bench``, the evaluate
sidecar, ``chip_smoke.py``): the cache directory is placed from
OUTSIDE the program.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this module never touches
``jax_compilation_cache_dir``; otherwise the cache lives at one fixed
path inside the checkout.  The path is part of the cache key, so a
directory that moves between runs never hits — which is why no flag
relocates it (``--compile-cache`` places the lowering entries only).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: a function of where the package sits, so two
# processes of one checkout always agree on it
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_xla_cache() -> str:
    """Enable the persistent XLA cache and return its directory.  The
    size/compile-time floors are dropped so the small admission kernels
    (46 per library) cache too."""
    import jax

    path = os.environ.get(ENV_VAR, "")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
