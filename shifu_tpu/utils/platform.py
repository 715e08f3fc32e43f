"""JAX start-up shared by the CLI, tests, and driver entry points: which
platform, and where compiled programs are kept.

The platform is forced through jax.config BEFORE the backend initializes
(the config API wins over a JAX_PLATFORMS value read at import). Safe to
call multiple times; once a backend exists jax rejects a change loudly.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def force_platform(platform: Optional[str] = None, n_devices: Optional[int] = None) -> None:
    """Force `platform` (default: the JAX_PLATFORMS env var, if set) and
    optionally request n virtual host devices (CPU mesh testing)."""
    platform = platform or os.environ.get("JAX_PLATFORMS")
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    if not platform:
        return
    os.environ.setdefault("JAX_PLATFORMS", platform)
    import jax

    jax.config.update("jax_platforms", platform)


def checkout_cache_dir() -> str:
    """`<checkout>/.jax_cache` — fixed, because the directory is part of
    the cache key: a tempfile/pid/time-derived path never hits."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def place_compile_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache for a process entry
    point (bin/shifu, benchmarks/run.py — never at import of
    shifu_tpu, so tests keep jax's default of no cache).

    Where JAX_COMPILATION_CACHE_DIR is set, jax already reads it and this
    sets nothing (returns None). Otherwise the cache goes to
    `checkout_cache_dir()`; returns the path set. The CLI is one process
    per lifecycle step, so without this every step recompiles every
    program every time."""
    if os.environ.get(CACHE_ENV):
        return None
    import jax

    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
