"""Pallas TPU kernel for the counter-based Poisson-burst sampler.

Grid: ``(B, n_window_blocks)`` — each program materialises the cycles
of a block of 64-cycle sampling windows for one case, entirely in VMEM:
threefry-2x32 counters are rebuilt from ``broadcasted_iota`` (the
stream is a pure function of (window, onu), no state crosses tiles),
the ``Poisson(64λ)`` count scan runs per window, and each burst draw is
accumulated output-stationary — burst ``j``'s placement (top 6 bits of
word 0) is compared against every cycle row of its window, its
geometric length (word 1) added where it lands.

Distribution parameters (``packet_bits``, ``n_draws``) are
compile-time constants — a sweep has a handful of distinct values —
while the per-case keys and window-rate thresholds, the burst-length
thresholds and the seek offset ``win0`` stay runtime inputs in SMEM so
one compilation serves every chunk of every case.  Burst lengths count
integer thresholds (``ref.geometric_tables``) instead of evaluating
``log1p`` in the kernel, whose float32 result on a TPU differs from the
CPU's in the last ulp for some inputs.

The kernel intentionally avoids ``pltpu.prng_*``: the hand-rolled
threefry keeps the stream identical to the XLA oracle (``ref.py``) and
the sparse numpy host path, which is what makes results
backend-independent.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.traffic.ref import (
    _C240,
    _ROTS,
    KEY_WEYL_0,
    KEY_WEYL_1,
    WINDOW,
)

DEFAULT_BLOCK_WINDOWS = 4
_LANE = 128                       # TPU lane tiling for the trailing axis
_I0 = np.int32(0)                 # block index literal, int32 under x64


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32(k0, k1, c0, c1):
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_C240))
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for block in range(5):
        for r in _ROTS[block % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + jnp.uint32(block + 1)
    return x0, x1


def _traffic_kernel(keys_ref, thr_ref, glen_ref, win0_ref, out_ref, *,
                    block_windows: int, n_onus_pad: int, n_draws: int,
                    n_levels: int, packet_bits: float):
    i = pl.program_id(1)
    k0 = keys_ref[0, 0, 0].astype(jnp.uint32)
    k1 = keys_ref[0, 0, 1].astype(jnp.uint32)
    wshape = (block_windows, n_onus_pad)
    c0 = (win0_ref[0].astype(jnp.uint32)
          + (i * block_windows).astype(jnp.uint32)
          + lax.broadcasted_iota(jnp.uint32, wshape, 0))
    c1 = lax.broadcasted_iota(jnp.uint32, wshape, 1)

    def words(d):
        du = jnp.uint32(d)
        kd0 = k0 + du * jnp.uint32(KEY_WEYL_0)
        kd1 = k1 ^ (du * jnp.uint32(KEY_WEYL_1))
        return _threefry2x32(kd0, kd1, c0, c1)

    # window burst count: integer inverse CDF over the host-built
    # threshold table, k = #{ j : bits24 > T_j }
    w0, _ = words(0)
    b24 = (w0 >> jnp.uint32(8)).astype(jnp.int32)

    def pois_body(j, count):
        return count + (b24 > thr_ref[0, 0, j]).astype(jnp.int32)

    count = lax.fori_loop(
        jnp.int32(0), jnp.int32(n_draws), pois_body,
        jnp.zeros(wshape, jnp.int32)
    )

    n_cyc = block_windows * WINDOW
    cyc_in_win = lax.broadcasted_iota(
        jnp.int32, (n_cyc, n_onus_pad), 0
    ) & jnp.int32(WINDOW - 1)

    def expand(x):
        """(windows, onus) -> (windows*64 cycles, onus)."""
        return jnp.broadcast_to(
            x[:, None, :], (block_windows, WINDOW, n_onus_pad)
        ).reshape(n_cyc, n_onus_pad)

    count_c = expand(count)

    def burst_body(j, packets):
        x0, x1 = words(j)
        place = (x0 >> jnp.uint32(32 - 6)).astype(jnp.int32)
        u24 = (x1 >> jnp.uint32(8)).astype(jnp.int32)

        def level(n, g):
            return g + (u24 >= glen_ref[0, n]).astype(jnp.int32)

        glen = (1 + lax.fori_loop(jnp.int32(0), jnp.int32(n_levels),
                                  level, jnp.zeros(wshape, jnp.int32))
                ).astype(jnp.float32)
        hit = (expand(place) == cyc_in_win) & (j <= count_c)
        return packets + jnp.where(hit, expand(glen), jnp.float32(0.0))

    packets = lax.fori_loop(
        jnp.int32(1), jnp.int32(n_draws + 1), burst_body,
        jnp.zeros((n_cyc, n_onus_pad), jnp.float32),
    )
    out_ref[0, :, :] = packets * jnp.float32(packet_bits)


def sample_arrival_bits_tpu(keys, cycle0: int, thresholds, burst_thr, *,
                            n_cycles: int, n_onus: int, n_draws: int,
                            packet_bits: float,
                            block_windows: int = DEFAULT_BLOCK_WINDOWS,
                            interpret: bool = False):
    """Arrival bits ``(B, n_cycles, n_onus)`` float32 via the TPU kernel.

    ``keys`` uint32 ``(B, 2)``; ``thresholds`` int32 ``(B, n_draws)``
    from ``ref.poisson_thresholds``; ``burst_thr`` int32 from
    ``ref.geometric_tables``; ``cycle0`` the absolute cycle of
    the first row. Only the intra-window offset (``cycle0 % 64``, at
    most 64 alignment classes) is compile-time; the window base stays a
    runtime input so one compilation serves every chunk of a stream.
    """
    win0 = cycle0 >> 6
    lo = cycle0 - (win0 << 6)
    return _sample_tpu_jit(
        keys, jnp.uint32(win0), thresholds, burst_thr, lo=lo,
        n_cycles=n_cycles, n_onus=n_onus, n_draws=n_draws,
        packet_bits=packet_bits, block_windows=block_windows,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("lo", "n_cycles", "n_onus", "n_draws",
                     "packet_bits", "block_windows", "interpret"),
)
def _sample_tpu_jit(keys, win0, thresholds, burst_thr, *, lo: int,
                    n_cycles: int, n_onus: int, n_draws: int,
                    packet_bits: float, block_windows: int,
                    interpret: bool):
    B = keys.shape[0]
    n_win = ((lo + n_cycles - 1) >> 6) + 1
    bw = min(block_windows, n_win)
    n_win_pad = math.ceil(n_win / bw) * bw
    n_onu_pad = math.ceil(n_onus / _LANE) * _LANE
    grid = (B, n_win_pad // bw)
    # keys, thresholds and the window base are scalars read by the
    # kernel body, so they ride in SMEM (keys as int32 bit patterns)
    win0_arr = lax.bitcast_convert_type(
        jnp.reshape(jnp.asarray(win0, jnp.uint32), (1,)), jnp.int32)
    n_levels = burst_thr.shape[0]

    def smem_row(width):              # this case's (1, width) row
        return pl.BlockSpec((1, 1, width), lambda b, i: (b, _I0, _I0),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(
            _traffic_kernel,
            block_windows=bw, n_onus_pad=n_onu_pad, n_draws=n_draws,
            n_levels=n_levels, packet_bits=packet_bits,
        ),
        grid=grid,
        in_specs=[smem_row(2), smem_row(n_draws),
                  pl.BlockSpec((1, n_levels), lambda b, i: (_I0, _I0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1,), lambda b, i: (_I0,),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(
            (1, bw * WINDOW, n_onu_pad), lambda b, i: (b, i, _I0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (B, n_win_pad * WINDOW, n_onu_pad), jnp.float32
        ),
        interpret=interpret,
    )(lax.bitcast_convert_type(jnp.asarray(keys, jnp.uint32),
                               jnp.int32)[:, None, :],
      jnp.asarray(thresholds, jnp.int32)[:, None, :],
      jnp.asarray(burst_thr, jnp.int32)[None, :], win0_arr)
    return out[:, lo:lo + n_cycles, :n_onus]
