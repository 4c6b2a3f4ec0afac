"""Public counter-based traffic sampling ops.

``sample_arrival_bits`` is the engine-facing entry point: given a batch
of stream keys it materialises any ``(cycle0, n_cycles)`` window of the
per-ONU background arrival process, identically regardless of how the
caller chunks the window (regression-tested).

Three interchangeable backends produce the *bit-identical* stream:

* ``"pallas"`` — the TPU kernel (``kernel.py``; ``"pallas_interpret"``
  runs it through the interpreter for CI parity tests);
* ``"xla"`` — the jitted pure-jnp oracle (``ref.py``);
* ``"numpy"`` — the sparse host path, default off-TPU: the uniform
  *bits* come from a vectorised numpy threefry (integer, exact), while
  every float mapping from bits to samples goes through XLA-evaluated
  tables (Poisson CDF prefix, geometric burst-length LUT), so no host
  libm ulp difference can leak into the stream. Burst lengths are only
  drawn for the ~``1-exp(-λ)`` fraction of nonzero cells, which is what
  makes this path faster than the dense draws it replaces.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.kernels.traffic import ref as _ref

_MASK32 = 0xFFFFFFFF
_ROTS = _ref._ROTS


# Weyl constants mixing the PON index into a stream key (murmur3 c1/c2;
# deliberately distinct from ref.KEY_WEYL_* so a pon-shifted stream can
# never alias another stream's per-draw derived keys).
_PON_WEYL_0 = 0xCC9E2D51
_PON_WEYL_1 = 0x1B873593

# Weyl constants mixing a tenant-job index into a stream key (murmur3
# final-avalanche / xorshift-mult constants; distinct from both the PON
# pair above and ref.KEY_WEYL_* for the same no-aliasing reason).
_JOB_WEYL_0 = 0xC2B2AE35
_JOB_WEYL_1 = 0x27D4EB2F


def make_stream_key(seed: int, phase: int, round_index: int = 0,
                    pon: int = 0, job: int = 0) -> np.ndarray:
    """uint32 ``(2,)`` key for one case's (phase, round, pon, job) stream.

    ``seed`` fills one key word, ``(phase, round)`` the other, and the
    PON and job indices Weyl-shift both words; threefry does the
    mixing. Distinct (seed, phase, round, pon, job) tuples therefore
    get independent streams, and a stream's values depend on nothing
    else — the O(1)-seek contract. ``pon=0`` reproduces the
    pre-multi-PON key bit-for-bit, and ``job=0`` the pre-multi-job key
    (both pinned by the stream regressions).
    """
    return np.array(
        [
            (seed + pon * _PON_WEYL_0 + job * _JOB_WEYL_0) & _MASK32,
            (phase + 2 * round_index + pon * _PON_WEYL_1
             + job * _JOB_WEYL_1) & _MASK32,
        ],
        np.uint32,
    )


def _tail_bound(lam_w: float) -> int:
    """Draw budget with negligible truncated Poisson tail mass for the
    per-*window* burst rate.

    ``λ_w + 12·sqrt(λ_w+1) + 8`` puts the truncation point ≥12 standard
    deviations above the mean (tail < 1e-20); rounded up to a multiple
    of 8 so distinct rates share compilations.
    """
    k = int(math.ceil(lam_w + 12.0 * math.sqrt(lam_w + 1.0) + 8.0))
    return max(8, int(math.ceil(k / 8.0)) * 8)


# ---------------------------------------------------------------------------
# numpy host path
# ---------------------------------------------------------------------------


def threefry2x32_np(k0, k1, c0, c1):
    """Vectorised numpy Threefry-2x32 (bit-identical to ``ref.py``)."""
    k0 = np.asarray(k0, np.uint32)
    k1 = np.asarray(k1, np.uint32)
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(_ref._C240))
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


_TF_BLOCK = 1 << 15               # L2-resident working-set per pass


def _threefry_blocked(k0: int, k1: int, c0_flat, c1_flat, out0, out1,
                      tmp):
    """In-place blocked Threefry-2x32 for one *scalar* key pair.

    The dense draw-0 pass is the sampler's hot loop; the ~110 elementwise
    passes per call are memory-allocation-bound at full array size, so
    the state arrays are walked in L2-sized blocks with preallocated
    scratch (no temporaries, ~cache-resident traffic).
    """
    k0, k1 = int(k0), int(k1)
    ks = (k0, k1, k0 ^ k1 ^ _ref._C240)
    inj = [(np.uint32(ks[(b + 1) % 3]),
            np.uint32((ks[(b + 2) % 3] + b + 1) & _MASK32))
           for b in range(5)]
    n = len(c0_flat)
    for s in range(0, n, _TF_BLOCK):
        e = min(s + _TF_BLOCK, n)
        x0 = out0[s:e]
        x1 = out1[s:e]
        t = tmp[: e - s]
        np.add(c0_flat[s:e], np.uint32(ks[0]), out=x0)
        np.add(c1_flat[s:e], np.uint32(ks[1]), out=x1)
        for block in range(5):
            for r in _ROTS[block % 2]:
                np.add(x0, x1, out=x0)
                np.right_shift(x1, np.uint32(32 - r), out=t)
                np.left_shift(x1, np.uint32(r), out=x1)
                np.bitwise_or(x1, t, out=x1)
                np.bitwise_xor(x1, x0, out=x1)
            np.add(x0, inj[block][0], out=x0)
            np.add(x1, inj[block][1], out=x1)


_cdf_cache: Dict[Tuple[bytes, int], np.ndarray] = {}


def _poisson_thresholds(lam_w: np.ndarray, n_draws: int) -> np.ndarray:
    key = (lam_w.tobytes(), n_draws)
    if key not in _cdf_cache:
        if len(_cdf_cache) > 64:
            _cdf_cache.clear()
        _cdf_cache[key] = _ref.poisson_thresholds(lam_w, n_draws)
    return _cdf_cache[key]


_BLOCK_OFF = 1 << 25              # > 2**24: per-case searchsorted offset


def _threefry_keys_blocked(kd0, kd1, c0, c1):
    """Blocked in-place Threefry-2x32 for per-element key arrays (the
    ragged burst-length draws, where the draw index varies per cell)."""
    n = len(c0)
    ks2 = kd0 ^ kd1 ^ np.uint32(_ref._C240)
    out0 = np.empty(n, np.uint32)
    out1 = np.empty(n, np.uint32)
    tmp = np.empty(min(_TF_BLOCK, n), np.uint32)
    for s in range(0, n, _TF_BLOCK):
        e = min(s + _TF_BLOCK, n)
        x0 = out0[s:e]
        x1 = out1[s:e]
        t = tmp[: e - s]
        ks = (kd0[s:e], kd1[s:e], ks2[s:e])
        np.add(c0[s:e], ks[0], out=x0)
        np.add(c1[s:e], ks[1], out=x1)
        for block in range(5):
            for r in _ROTS[block % 2]:
                np.add(x0, x1, out=x0)
                np.right_shift(x1, np.uint32(32 - r), out=t)
                np.left_shift(x1, np.uint32(r), out=x1)
                np.bitwise_or(x1, t, out=x1)
                np.bitwise_xor(x1, x0, out=x1)
            np.add(x0, ks[(block + 1) % 3], out=x0)
            np.add(x1, ks[(block + 2) % 3], out=x1)
            np.add(x1, np.uint32(block + 1), out=x1)
    return out0, out1


@functools.lru_cache(maxsize=8)
def _counter_templates(n_win: int, n_onus: int):
    return (
        np.repeat(np.arange(n_win, dtype=np.int64), n_onus),
        np.tile(np.arange(n_onus, dtype=np.uint32), n_win),
    )


def _window_counts(keys, win0, n_win, n_onus, lam_arr, n_draws):
    """Burst count per (case, window, onu): dense draw-0 threefry plus
    an offset-blocked integer searchsorted against each case's f64
    Poisson threshold table."""
    B = keys.shape[0]
    n_flat = n_win * n_onus
    c0_base, c1_flat = _counter_templates(n_win, n_onus)
    c0_flat = ((win0 + c0_base) & _MASK32).astype(np.uint32)
    w0 = np.empty((B, n_flat), np.uint32)
    w1 = np.empty((B, n_flat), np.uint32)
    tmp = np.empty(min(_TF_BLOCK, n_flat), np.uint32)
    for b in range(B):
        # word 1 of draw 0 is unused (the count consumes word 0 only)
        _threefry_blocked(keys[b, 0], keys[b, 1], c0_flat, c1_flat,
                          w0[b], w1[b], tmp)
    tables = _poisson_thresholds(
        np.asarray(lam_arr, np.float64) * _ref.WINDOW, n_draws
    ).astype(np.int64)
    table_all = (tables
                 + np.arange(B, dtype=np.int64)[:, None] * _BLOCK_OFF
                 ).ravel()
    b24 = (w0 >> np.uint32(8)).astype(np.int64)
    b24 += np.arange(B, dtype=np.int64)[:, None] * _BLOCK_OFF
    cnt = (np.searchsorted(table_all, b24.reshape(-1), side="left")
           - np.repeat(np.arange(B, dtype=np.int64), n_flat) * n_draws)
    return cnt, c0_flat, c1_flat


def _burst_groups(cnt):
    """(flat cell, draw index) pairs for every burst, via cumsum tricks
    (no ``np.repeat`` over the ragged axis)."""
    nz = np.flatnonzero(cnt)
    kk = cnt[nz]
    total = int(kk.sum())
    starts = np.zeros(len(nz), np.int64)
    np.cumsum(kk[:-1], out=starts[1:])
    step = np.zeros(total, np.int64)
    step[starts[1:]] = 1
    src = nz[np.cumsum(step)]
    dstep = np.ones(total, np.int64)
    dstep[starts[1:]] = 1 - kk[:-1]
    du = np.cumsum(dstep).astype(np.uint32)
    return src, du


def _burst_bits(keys, cnt, c0_flat, c1_flat, cycle0, win0, n_cycles,
                n_onus, n_flat, inv_burst):
    """Place and size every burst: one keyed threefry per burst — word 0
    places it on a cycle (top 6 bits, exactly uniform over the window),
    word 1 draws its geometric length — accumulated with bincount."""
    B = keys.shape[0]
    src, du = _burst_groups(cnt)
    g_b = src // n_flat
    g_f = src - g_b * n_flat
    kd0 = keys[g_b, 0] + du * np.uint32(_ref.KEY_WEYL_0)
    kd1 = keys[g_b, 1] ^ (du * np.uint32(_ref.KEY_WEYL_1))
    x0, x1 = _threefry_keys_blocked(
        kd0, kd1, c0_flat[g_f], c1_flat[g_f]
    )
    place = (x0 >> np.uint32(32 - 6)).astype(np.int64)
    glen = _ref.geometric_tables(float(inv_burst))[0][x1 >> np.uint32(8)]
    win_i = g_f // n_onus
    onu_i = g_f - win_i * n_onus
    cyc = (win_i << 6) + place - (cycle0 - (win0 << 6))
    ok = (cyc >= 0) & (cyc < n_cycles)
    dest = (g_b * n_cycles + cyc) * n_onus + onu_i
    return np.bincount(
        dest[ok], weights=glen[ok], minlength=B * n_cycles * n_onus,
    )


def _sample_numpy(keys, cycle0, lam_arr, inv_burst, packet_bits,
                  n_cycles, n_onus, n_draws):
    B = keys.shape[0]
    win0 = cycle0 >> 6
    n_win = ((cycle0 + n_cycles - 1) >> 6) - win0 + 1
    cnt, c0_flat, c1_flat = _window_counts(
        keys, win0, n_win, n_onus, lam_arr, n_draws
    )
    if cnt.any():
        out_flat = _burst_bits(
            keys, cnt, c0_flat, c1_flat, cycle0, win0, n_cycles,
            n_onus, n_win * n_onus, inv_burst,
        )
    else:
        out_flat = np.zeros(B * n_cycles * n_onus)
    out = out_flat.reshape(B, n_cycles, n_onus)
    return out * float(packet_bits)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("cycle0", "n_cycles", "n_onus", "n_draws"),
)
def _sample_xla(keys, thresholds, burst_thr, packet_bits, *, cycle0,
                n_cycles, n_onus, n_draws):
    return _ref.sample_arrival_bits_ref(
        keys, cycle0, thresholds, burst_thr, packet_bits,
        n_cycles=n_cycles, n_onus=n_onus, n_draws=n_draws,
    )


def sample_arrival_bits(keys, cycle0: int, n_cycles: int, n_onus: int,
                        lam, inv_burst: float, packet_bits: float,
                        backend: Optional[str] = None) -> np.ndarray:
    """Arrival bits ``(B, n_cycles, n_onus)`` float64 numpy.

    ``keys``: uint32 ``(B, 2)`` (or ``(2,)`` for B=1); ``lam``: per-case
    per-cycle burst rate, scalar or ``(B,)``. ``backend``: ``None``
    auto-selects (Pallas on TPU, the sparse numpy path elsewhere);
    ``"numpy"``, ``"xla"``, ``"pallas"`` and ``"pallas_interpret"``
    force a path — all produce the identical stream (tested).
    """
    keys = np.atleast_2d(np.asarray(keys, np.uint32))
    lam_arr = np.ascontiguousarray(np.broadcast_to(
        np.asarray(lam, np.float32), (keys.shape[0],)
    ))
    lam_max = float(lam_arr.max())
    if lam_max <= 0.0:
        return np.zeros((keys.shape[0], n_cycles, n_onus))
    n_draws = _tail_bound(lam_max * _ref.WINDOW)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend == "numpy":
        return _sample_numpy(
            keys, cycle0, lam_arr, inv_burst, packet_bits,
            n_cycles, n_onus, n_draws,
        )
    thresholds = _poisson_thresholds(
        np.asarray(lam_arr, np.float64) * _ref.WINDOW, n_draws
    )
    burst_thr = _ref.geometric_tables(float(inv_burst))[1]
    if backend == "xla":
        out = _sample_xla(
            keys, thresholds, burst_thr, packet_bits,
            cycle0=int(cycle0),
            n_cycles=n_cycles, n_onus=n_onus, n_draws=n_draws,
        )
    elif backend in ("pallas", "pallas_interpret"):
        from repro.kernels.traffic.kernel import sample_arrival_bits_tpu

        out = sample_arrival_bits_tpu(
            keys, int(cycle0), thresholds, burst_thr,
            n_cycles=n_cycles, n_onus=n_onus, n_draws=n_draws,
            packet_bits=float(packet_bits),
            interpret=(backend == "pallas_interpret"),
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return np.asarray(out, np.float64)
