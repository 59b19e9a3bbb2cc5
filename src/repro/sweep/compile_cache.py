"""Persistent JAX compilation cache for repeat campaign invocations.

The megabatch runner already amortizes jit compiles *within* a process (one
compile per pipeline shape); this module makes them survive *across*
processes: compiled executables are written to an on-disk cache keyed by the
XLA computation fingerprint -- which for this engine is exactly the pipeline
shape (tree size, scheme modes, bucketed packet count, JSQ padding, backend,
device mesh) -- so re-running a campaign, or running a different campaign
whose grid lands in the same shape buckets, skips compilation entirely.

The cache location, in precedence order:

1. JAX's own ``JAX_COMPILATION_CACHE_DIR`` environment variable -- when it
   is set, the cache lives there and nowhere else;
2. the path the caller asks for: ``run_campaign(compile_cache_dir=...)``,
   or :data:`DEFAULT_DIR` (``<checkout>/jax-cache``) for the CLI and
   ``chip_smoke.py``.  The path is fixed because it is part of the cache
   key: a directory that moves with every output directory never hits.

``compile_cache_dir=False`` (the CLI's ``--no-compile-cache``) turns the
cache off, even when the environment variable is set.  A cache that cannot
be set up raises; the campaign never runs uncached without saying so.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/jax-cache: this file is <checkout>/src/repro/sweep/.
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / "jax-cache")
_enabled_dir: Optional[str] = None


def resolve(path: Optional[str] = None) -> Optional[str]:
    """The directory :func:`enable` would use: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``path`` (None: no persistent cache)."""
    return os.environ.get(ENV_VAR) or (str(path) if path else None)


def _reset() -> None:
    # JAX initializes its cache singleton lazily on the first compile; if
    # anything compiled before a config change, that singleton still holds
    # the old setting and config updates alone would be ignored.
    from jax.experimental.compilation_cache import compilation_cache as _cc
    _cc.reset_cache()


def enable(path: Optional[str] = None) -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`resolve` ``(path)``
    and return that directory (None when there is none to use).

    Thresholds are dropped to zero so even the small CPU-CI pipelines cache;
    entries are content-addressed, so sharing one directory across campaigns
    and topologies is safe.
    """
    global _enabled_dir
    path = resolve(path)
    if not path:
        return None
    if _enabled_dir == path:
        return _enabled_dir
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _reset()
    _enabled_dir = path
    return _enabled_dir


def disable() -> None:
    """Turn the persistent cache off for this process."""
    global _enabled_dir
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    _reset()
    _enabled_dir = None


def active_dir() -> Optional[str]:
    return _enabled_dir
