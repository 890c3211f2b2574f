"""Persistent XLA compilation cache, and the racon_tpu cache root.

The device kernels' first compile costs seconds per shape; the
reference's CUDA kernels are precompiled at build time so it pays this
cost never.  JAX's persistent compilation cache amortises our compiles
across processes the same way (first run pays, every later run loads
from disk).

The XLA cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, when it is
set, and otherwise to ``<checkout>/.jax_cache`` (gitignored).  A fixed
path matters: the path is part of the cache's key, so a directory that
moves never hits.  ``RACON_TPU_CACHE_DIR`` names the root of racon's
own caches (result cache, AOT shelf, calibration); it does not move
the XLA cache.
"""

from __future__ import annotations

import os

_enabled = False


def cache_root():
    """The racon_tpu cache ROOT directory (holding the aot/ subdir,
    results/ and calibration.json), honoring RACON_TPU_CACHE_DIR:
    unset -> ~/.cache/racon_tpu, empty (or unexpanded '~' when HOME is
    unset) -> None = caching disabled.  A custom value names the root
    itself."""
    path = os.environ.get(
        "RACON_TPU_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "racon_tpu"))
    if not path or path.startswith("~"):
        return None
    return path.rstrip("/") or None


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compilation_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
    path ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compilation_cache() -> None:
    global _enabled
    if _enabled:
        return
    _enabled = True
    path = compilation_cache_dir()
    import jax

    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return  # cache is an optimization; never fail the run for it
    # set even when it came from the environment: jax reads the
    # variable once, at import, which may precede the caller's setenv
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
