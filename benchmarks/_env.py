"""Environment provenance stamped into benchmark artifacts.

Every BENCH_*.json payload carries a ``meta`` block (jax version,
backend, devices, host platform) so a number can be traced to the
machine and stack that produced it.  The regression gate
(``benchmarks/compare.py``) extracts only throughput metrics and
ignores the block entirely — metadata never participates in
comparisons.
"""
from __future__ import annotations

import os
import platform


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives at the fixed
    path ``<root>/.jax_cache`` (a fixed path, because the directory is
    part of the cache key).  Returns the directory in use.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def env_metadata() -> dict:
    """jax/backend/device + host info, best-effort (never raises)."""
    meta = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "machine": platform.machine(),
    }
    try:
        import jax

        meta["jax"] = jax.__version__
        meta["backend"] = jax.default_backend()
        devices = jax.devices()
        meta["device_count"] = len(devices)
        meta["device_kind"] = devices[0].device_kind if devices else None
    except Exception as e:  # pragma: no cover - jax-less environments
        meta["jax_error"] = f"{type(e).__name__}: {e}"
    try:
        import numpy as np

        meta["numpy"] = np.__version__
    except Exception:  # pragma: no cover
        pass
    return meta


def stamp(payload: dict) -> dict:
    """Attach ``meta`` to a benchmark payload (in place, returned)."""
    payload.setdefault("meta", env_metadata())
    return payload
