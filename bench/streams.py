"""Background traffic of the reference: counter-based Poisson bursts.

A copy of the program's traffic model (``src/repro/kernels/traffic``:
``ref.py`` and the numpy path of ``ops.py``), kept here so that the
yardstick does not move when the program does.  Bursts arrive per ONU
as a Poisson process, ``Poisson(64 * lam)`` of them per 64-cycle
window, each placed uniformly on a cycle of its window and a geometric
number of 1500 B packets long (mean 16 in every configuration here).
Every draw is a Threefry-2x32 of the ``(window, onu)`` counter under a
key made from ``(seed, phase, round, pon)`` and the draw index, so any
cycle of any stream can be read on its own.
"""
from __future__ import annotations

import functools
import math

import numpy as np

PACKET_BITS = 1500 * 8
WINDOW = 64
_C240 = 0x1BD11BDA
_ROTS = ((13, 15, 26, 6), (17, 29, 16, 24))
_KEY_WEYL = (0x9E3779B9, 0x85EBCA6B)      # per-draw key derivation
_PON_WEYL = (0xCC9E2D51, 0x1B873593)      # PON index into a stream key
_MASK32 = 0xFFFFFFFF


def stream_key(seed: int, phase: int, round_index: int, pon: int):
    """uint32 ``(2,)`` key of one (case, phase, round, PON) stream;
    ``phase`` 0 is downstream, 1 upstream."""
    return np.array([(seed + pon * _PON_WEYL[0]) & _MASK32,
                     (phase + 2 * round_index + pon * _PON_WEYL[1])
                     & _MASK32], np.uint32)


def burst_lambda(rate_bps: float, cycle_s: float, burst_packets: float):
    """Bursts per cycle for a per-ONU bit rate, as float32."""
    if rate_bps <= 0:
        return np.float32(0.0)
    return np.float32(rate_bps / (PACKET_BITS * burst_packets) * cycle_s)


def threefry2x32(k0, k1, c0, c1):
    """20-round Threefry-2x32 over broadcastable uint32 arrays."""
    k0 = np.asarray(k0, np.uint32)
    k1 = np.asarray(k1, np.uint32)
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(_C240))
    x0 = np.asarray(c0, np.uint32) + ks[0]
    x1 = np.asarray(c1, np.uint32) + ks[1]
    for block in range(5):
        for r in _ROTS[block % 2]:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def draw_budget(lam_w: float) -> int:
    """Draws per window: at least 12 standard deviations past the mean
    of ``Poisson(lam_w)``, rounded up to a multiple of 8."""
    k = int(math.ceil(lam_w + 12.0 * math.sqrt(lam_w + 1.0) + 8.0))
    return max(8, int(math.ceil(k / 8.0)) * 8)


@functools.lru_cache(maxsize=16)
def poisson_thresholds(lam_w: float, n_draws: int):
    """``T_j = floor(CDF(j) * 2**24)`` of ``Poisson(lam_w)`` in float64
    log space; a window holds ``#{j : u24 > T_j}`` bursts."""
    j = np.arange(n_draws, dtype=np.float64)
    logfact = np.concatenate([[0.0],
                              np.cumsum(np.log(np.arange(1.0, n_draws)))])
    if lam_w <= 0.0:
        lpmf = np.full(n_draws, -np.inf)
        lpmf[0] = 0.0
    else:
        lpmf = -lam_w + j * np.log(lam_w) - logfact
    cdf = np.cumsum(np.exp(lpmf))
    return np.floor(np.minimum(cdf, 1.0) * float(1 << 24)).astype(np.int64)


@functools.lru_cache(maxsize=4)
def burst_lengths(inv_burst: float):
    """int32 ``(2**24,)``: geometric burst length of each 24-bit uniform,
    ``1 + floor(log1p(-u) / log1p(-p))`` as XLA's float32 computes it on
    the CPU, which is where the stream is defined."""
    import jax
    import jax.numpy as jnp

    def lut(p):
        u = jnp.arange(1 << 24, dtype=jnp.uint32).astype(jnp.float32) * (
            jnp.float32(1.0 / (1 << 24)))
        inv_log_q = jnp.float32(1.0) / jnp.log1p(-p)
        return (jnp.float32(1.0)
                + jnp.floor(jnp.log1p(-u) * inv_log_q)).astype(jnp.int32)

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        return np.asarray(jax.jit(lut)(jnp.float32(inv_burst)))


def arrival_packets(key, lam, cycle0: int, n_cycles: int, n_onus: int,
                    burst_packets: float):
    """Packets arriving per ``(cycle, onu)`` for cycles
    ``[cycle0, cycle0 + n_cycles)``, int64; ``cycle0`` and ``n_cycles``
    are whole windows."""
    assert cycle0 % WINDOW == 0 and n_cycles % WINDOW == 0
    out = np.zeros((n_cycles, n_onus), np.int64)
    lam = float(np.float32(lam))
    if lam <= 0.0:
        return out
    lam_w = lam * WINDOW
    n_draws = draw_budget(lam_w)
    thr = poisson_thresholds(lam_w, n_draws)
    lengths = burst_lengths(1.0 / burst_packets)
    n_win = n_cycles // WINDOW
    k0, k1 = int(key[0]), int(key[1])
    c0 = (np.uint32(cycle0 // WINDOW)
          + np.arange(n_win, dtype=np.uint32))[:, None]
    c1 = np.arange(n_onus, dtype=np.uint32)[None, :]
    c0, c1 = np.broadcast_arrays(c0, c1)
    w0, _ = threefry2x32(k0, k1, c0, c1)
    count = np.searchsorted(thr, (w0 >> np.uint32(8)).astype(np.int64),
                            side="left")
    for d in range(1, int(count.max()) + 1):
        win, onu = np.nonzero(count >= d)
        kd0 = (k0 + d * _KEY_WEYL[0]) & _MASK32
        kd1 = k1 ^ ((d * _KEY_WEYL[1]) & _MASK32)
        x0, x1 = threefry2x32(kd0, kd1, c0[win, onu], c1[win, onu])
        cyc = win * WINDOW + (x0 >> np.uint32(32 - 6)).astype(np.int64)
        np.add.at(out, (cyc, onu), lengths[x1 >> np.uint32(8)])
    return out
