"""Process set-up shared by every cell: the compile cache, the compile
counter and the table of chip peaks.

``use_compile_cache`` follows ``benchmarks/_env.use_compile_cache`` and
``Compiles`` follows ``chip_smoke.Compiles``; both are copied so that the
yardstick does not depend on files that later changes may edit.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def use_compile_cache(root: str) -> str:
    """Keep JAX's persistent compilation cache at ``<root>/.jax_cache``.

    Must run before JAX is imported.  The path is fixed, because the
    directory is part of the cache key, and lies inside the checkout, so
    that two checkouts never share a cache.  Every program is cached,
    however short its compile, so that only a checkout's first run
    compiles.
    """
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return path


class Compiles:
    """Backend compilations JAX reports (count and seconds); register
    with ``jax.monitoring.register_event_duration_secs_listener``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind without published peaks is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(table)}")
    return table[device_kind]
