"""Pure-jnp oracle for the counter-based Poisson-burst sampler.

The sampler is a keyed pure function ``(key, onu, cycle) -> bits``,
organised around fixed 64-cycle *windows* (Poisson-process thinning:
``Poisson(64λ)`` bursts per window placed conditionally-uniformly over
its 64 cycles is exactly iid ``Poisson(λ)`` bursts per cycle — the same
law as per-cycle draws, at 1/64th the dense randomness):

* draw 0 of a ``(window, onu)`` counter drives the window's burst count
  via bounded inverse-CDF summation over ``Poisson(64λ)``;
* draw ``j ≥ 1`` yields burst ``j``: output word 0 places it on a cycle
  (top 6 bits — exactly uniform over 64), word 1 draws its
  geometric(1/burst) packet length via the exact inverse CDF. The
  per-cycle packet total is ``Σ_bursts length·[placed here]`` — the
  ``k + NB(k, 1/burst)`` law of the numpy draws it replaces, without
  sequential state.

The draw index is folded into the threefry *key* (Weyl increments), the
``(window, onu)`` pair is the *counter*, so any cycle range is
O(1)-seekable and chunk boundaries can never change the stream. Burst
counts go through host-built integer thresholds
(:func:`poisson_thresholds`). Burst lengths are defined by a float32
LUT (:func:`geometric_lut`) that XLA evaluates on the CPU backend; the
device paths count integer thresholds of that LUT
(:func:`geometric_tables`, :func:`burst_length`) instead of evaluating
``log1p`` themselves, because a TPU's float32 ``log1p`` differs from the
CPU's in the last ulp for some inputs. So the Pallas kernels, this
oracle and the sparse numpy host path (``ops.py``) reproduce the stream
bit-for-bit on every backend (tested).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Threefry-2x32 constants (Random123 / JAX's PRNG).
_C240 = 0x1BD11BDA
_ROTS = ((13, 15, 26, 6), (17, 29, 16, 24))
# Weyl-style per-draw key derivation constants (golden-ratio / murmur3).
KEY_WEYL_0 = 0x9E3779B9
KEY_WEYL_1 = 0x85EBCA6B
UNIT_SCALE = 1.0 / (1 << 24)      # top-24-bit uniform in [0, 1)
WINDOW = 64                       # cycles per sampling window
_WIN_SHIFT = 6


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32_ref(k0, k1, c0, c1):
    """Standard 20-round Threefry-2x32 over broadcastable uint32 arrays.

    Returns the two output words; matches
    ``jax.extend.random.threefry_2x32`` bit-for-bit (tested).
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_C240))
    x0 = jnp.asarray(c0, jnp.uint32) + ks[0]
    x1 = jnp.asarray(c1, jnp.uint32) + ks[1]
    for block in range(5):
        for r in _ROTS[block % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + jnp.uint32(block + 1)
    return x0, x1


def draw_key(k0, k1, d):
    """Per-draw derived key: draw ``d`` of a stream is an independent
    threefry instance (Weyl-incremented key words)."""
    d = jnp.asarray(d, jnp.uint32)
    return (k0 + d * jnp.uint32(KEY_WEYL_0),
            k1 ^ (d * jnp.uint32(KEY_WEYL_1)))


def poisson_thresholds(lam_w, n_draws: int):
    """int32 ``(B, n_draws)`` inverse-CDF thresholds for the window
    burst count: ``count = #{ j : bits24 > T_j }`` with
    ``T_j = floor(CDF_Poisson(λ_w)(j) · 2²⁴)``.

    Computed host-side in float64 log space (stable for any λ_w — a
    float32 pmf recurrence underflows to denormal garbage beyond
    λ_w ≈ 90) and shared verbatim by every backend, so burst counts are
    integer-exact and bit-identical everywhere. f64 error (~1e-13) is
    far below the 2⁻²⁴ threshold quantum.
    """
    import numpy as _np

    lam_w = _np.asarray(lam_w, _np.float64).reshape(-1)
    j = _np.arange(n_draws, dtype=_np.float64)
    logfact = _np.concatenate(
        [[0.0], _np.cumsum(_np.log(_np.arange(1.0, n_draws)))]
    )
    with _np.errstate(divide="ignore", invalid="ignore"):
        lpmf = (-lam_w[:, None] + j[None, :] * _np.log(lam_w)[:, None]
                - logfact[None, :])
    lpmf = _np.where(lam_w[:, None] > 0.0, lpmf, -_np.inf)
    lpmf[lam_w <= 0.0, 0] = 0.0    # λ=0: all mass at count 0
    cdf = _np.cumsum(_np.exp(lpmf), axis=1)
    return _np.floor(
        _np.minimum(cdf, 1.0) * float(1 << 24)
    ).astype(_np.int32)


@functools.partial(jax.jit, static_argnames=())
def geometric_lut(inv_burst):
    """int32 ``(2**24,)`` map from 24-bit uniform to a geometric(p)
    burst length: the inverse CDF in XLA float32 with a fixed operation
    order.  The stream is defined by its value on the CPU backend
    (:func:`geometric_tables`)."""
    inv_burst = jnp.asarray(inv_burst, jnp.float32)
    u = jnp.arange(1 << 24, dtype=jnp.uint32).astype(jnp.float32) * (
        jnp.float32(UNIT_SCALE)
    )
    inv_log_q = jnp.float32(1.0) / jnp.log1p(-inv_burst)
    return (jnp.float32(1.0)
            + jnp.floor(jnp.log1p(-u) * inv_log_q)).astype(jnp.int32)


@functools.lru_cache(maxsize=8)
def geometric_tables(inv_burst: float):
    """``(lut, thresholds)`` of the burst-length map for ``inv_burst``.

    ``lut`` is :func:`geometric_lut` evaluated on the CPU backend,
    whichever device is the default.  The map is non-decreasing in the
    24-bit uniform (checked), so it is exactly
    ``length(u24) = 1 + #{n : u24 >= thresholds[n]}`` with int32
    ``thresholds[n]`` the first ``u24`` whose length exceeds ``n + 1``
    — integer compares that every backend evaluates identically.  The
    device paths take that count directly, as a sum of compares against
    every threshold (:func:`burst_length`, the Pallas kernel), never by
    searching the table.
    """
    with jax.default_device(jax.devices("cpu")[0]):
        lut = np.asarray(geometric_lut(float(inv_burst)))
    if np.any(np.diff(lut) < 0):
        raise ValueError(f"burst-length LUT for inv_burst={inv_burst} "
                         "is not monotone")
    levels = np.arange(2, int(lut[-1]) + 1)
    thresholds = np.searchsorted(lut, levels, side="left").astype(np.int32)
    return lut, thresholds


# thresholds per block of :func:`burst_length`'s table: XLA:CPU splits a
# reduction over more than 32 elements of one axis into a tree that
# materialises every compare.  The blocks lead the draws' axes, so the
# TPU sums them without a layout copy of the draws.
_LEVEL_BLOCK = 32


def burst_length(x1, thresholds):
    """float32 burst lengths from threefry word 1 (its top 24 bits) and
    int32 :func:`geometric_tables` thresholds.

    The length is ``1 + #{n : u24 >= thresholds[n]}`` (what
    ``searchsorted(..., side="right")`` returns), taken as one broadcast
    compare against every threshold and an int32 sum: no loop and no
    gather, which XLA fuses into a single reduce.  The table goes in
    front of the draws' axes as ``(blocks, 32)``, padded with the int32
    maximum, which no 24-bit draw reaches, so the padding counts nothing.
    """
    u24 = (jnp.asarray(x1, jnp.uint32) >> jnp.uint32(8)).astype(jnp.int32)
    thresholds = jnp.asarray(thresholds, jnp.int32)
    table = jnp.pad(thresholds, (0, -thresholds.shape[0] % _LEVEL_BLOCK),
                    constant_values=jnp.iinfo(jnp.int32).max)
    table = table.reshape((-1, _LEVEL_BLOCK) + (1,) * u24.ndim)
    n = (u24 >= table).sum((0, 1), dtype=jnp.int32)
    return (n + 1).astype(jnp.float32)


def sample_arrival_bits_ref(keys, cycle0, thresholds, burst_thr,
                            packet_bits, *, n_cycles: int, n_onus: int,
                            n_draws: int):
    """Arrival bits ``(B, n_cycles, n_onus)`` float32.

    ``keys``: uint32 ``(B, 2)`` stream keys; ``thresholds``: int32
    ``(B, n_draws)`` from :func:`poisson_thresholds` (per-window burst
    count inverse CDF); ``burst_thr``: int32 burst-length thresholds
    from :func:`geometric_tables`.
    """
    keys = jnp.asarray(keys, jnp.uint32)
    thresholds = jnp.asarray(thresholds, jnp.int32)
    cycle0 = int(cycle0)
    win0 = cycle0 >> _WIN_SHIFT
    n_win = ((cycle0 + n_cycles - 1) >> _WIN_SHIFT) - win0 + 1
    k0 = keys[:, 0][:, None, None]
    k1 = keys[:, 1][:, None, None]
    c0 = (jnp.uint32(win0)
          + jnp.arange(n_win, dtype=jnp.uint32))[None, :, None]
    c1 = jnp.arange(n_onus, dtype=jnp.uint32)[None, None, :]

    # window burst count: integer inverse CDF, k = #{ j : bits > T_j }
    kd0, kd1 = draw_key(k0, k1, 0)
    w0, _ = threefry2x32_ref(kd0, kd1, c0, c1)
    b24 = (w0 >> jnp.uint32(8)).astype(jnp.int32)
    shape = b24.shape

    def pois_body(j, count):
        t_j = lax.dynamic_index_in_dim(
            thresholds, j, axis=1, keepdims=False
        )[:, None, None]
        return count + (b24 > t_j).astype(jnp.int32)

    count = lax.fori_loop(
        0, n_draws, pois_body, jnp.zeros(shape, jnp.int32)
    )

    # bursts: word 0 places (top 6 bits — exact uniform over the
    # window), word 1 draws the geometric length; accumulate densely
    slot = jnp.arange(WINDOW, dtype=jnp.int32)[None, None, :, None]

    def burst_body(j, packets):
        bd0, bd1 = draw_key(k0, k1, j)
        x0, x1 = threefry2x32_ref(bd0, bd1, c0, c1)
        place = (x0 >> jnp.uint32(32 - _WIN_SHIFT)).astype(jnp.int32)
        glen = burst_length(x1, burst_thr)
        live = (j <= count)
        hit = (place[:, :, None, :] == slot) & live[:, :, None, :]
        return packets + jnp.where(hit, glen[:, :, None, :],
                                   jnp.float32(0.0))

    packets = lax.fori_loop(
        1, n_draws + 1, burst_body,
        jnp.zeros((shape[0], n_win, WINDOW, n_onus), jnp.float32),
    )
    packets = packets.reshape(shape[0], n_win * WINDOW, n_onus)
    lo = cycle0 - (win0 << _WIN_SHIFT)
    return (packets[:, lo:lo + n_cycles, :]
            * jnp.asarray(packet_bits, jnp.float32))
