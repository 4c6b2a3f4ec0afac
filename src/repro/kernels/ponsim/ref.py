"""Pure-jnp oracle pieces for the on-device PON cycle engine.

The numpy engine (``repro.net.engine``) advances one polling cycle per
Python iteration; the jit backend (``ops.py``) re-expresses the whole
phase as one ``lax.while_loop`` device program. This module holds the
per-cycle *grant* primitives of that program in plain jnp — the exact
semantic mirrors of their numpy counterparts:

* :func:`waterfill_grants_ref` — oldest-first sequential
  ``take = min(backlog, cap)`` grants as stable argsort + prefix-sum
  room, including the numpy path's lazy skip (when total demand sits a
  bit under capacity every queue is granted its full backlog, bitwise —
  a ``lax.cond`` keeps that exactness AND skips the sort on device);
* :func:`cps_waterfill_ref` — the max-min CPS split across a case's
  PONs (``repro.net.multi_pon.cps_waterfill`` in jnp, same closed-form
  water level);
* :func:`sample_window_ref` — one 64-cycle window of the counter-based
  Poisson-burst sampler with a *traced* window index, so the scan can
  generate arrival bits on-device. It reuses the integer threefry and
  the integer count and burst-length thresholds of
  ``repro.kernels.traffic.ref`` verbatim,
  which is what makes the fused stream bit-identical to the host
  sampler (pinned by tests/test_ponsim_jit.py).

Everything here is dtype-explicit: the queue arithmetic runs in float64
(under the backend entry point's scoped x64 guard, ``ops.py``) while the
sampler stays uint32/float32 exactly like every other traffic backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.traffic.ref import (
    _WIN_SHIFT,
    WINDOW,
    burst_length,
    draw_key,
    threefry2x32_ref,
)

CAP_EPS = 1e-9        # repro.net.engine.CAP_EPS


def prefix_sum(x):
    """Inclusive prefix sum along axis 1 as a parallel scan.

    ``jnp.cumsum`` lowers to an O(N^2) reduce-window that the TPU
    compiler does not finish in bounded time for float64; the
    log-depth ``associative_scan`` compiles in well under a second."""
    return lax.associative_scan(jnp.add, x, axis=1)


def waterfill_grants_ref(backlog, hol, cap):
    """Oldest-first waterfill grants ``(R, N)`` — the jnp mirror of
    ``repro.net.engine._waterfill``.

    ``hol`` is any array that sorts queues by head-of-line age (float
    times with ``inf`` for empty queues, or integer arrival cycles);
    ``cap`` is the per-row cycle capacity ``(R,)``. When no row's total
    demand exceeds ``cap - 1`` every queue takes its full backlog
    *bitwise* (the numpy lazy path) and the sort is skipped on device
    too (``lax.cond``).
    """

    def _sorted(args):
        backlog, hol, cap = args
        R = backlog.shape[0]
        order = jnp.argsort(hol, axis=1, stable=True)
        rows = jnp.arange(R)[:, None]
        b_s = jnp.take_along_axis(backlog, order, axis=1)
        prefix = prefix_sum(b_s)
        room = cap[:, None] - (prefix - b_s)
        g_s = jnp.where(room > CAP_EPS, jnp.minimum(b_s, room), 0.0)
        g = jnp.zeros_like(backlog).at[rows, order].set(g_s)
        # rows under capacity keep the exact-backlog fast path (the
        # serve step detects full drains by float *equality*)
        hard = backlog.sum(axis=1) > cap - 1.0
        return jnp.where(hard[:, None], g, backlog)

    any_hard = jnp.any(backlog.sum(axis=1) > cap - 1.0)
    return lax.cond(any_hard, _sorted, lambda args: args[0],
                    (backlog, hol, cap))


def cps_waterfill_ref(want, cap):
    """Max-min fair CPS split, jnp mirror of
    ``repro.net.multi_pon.cps_waterfill`` for a ``(G, P)`` batch.

    Non-over rows return ``want`` unchanged (bitwise, like the numpy
    early-out); over rows sit at the exact water level
    ``eff_p = min(want_p, mu)``.
    """
    G, P = want.shape
    tot = want.sum(axis=1)
    over = tot > cap + CAP_EPS
    ws = jnp.sort(want, axis=1)
    cum = prefix_sum(ws)
    prev = cum - ws
    mu_k = (cap - prev) / (P - jnp.arange(P, dtype=want.dtype))
    kk = jnp.argmax(mu_k <= ws, axis=1)
    mu = jnp.take_along_axis(mu_k, kk[:, None], axis=1)
    return jnp.where(over[:, None], jnp.minimum(want, mu), want)


def sample_window_ref(keys, thresholds, win, *, n_onus: int,
                      n_draws: int, burst_thr, packet_bits):
    """Arrival bits ``(R, WINDOW, n_onus)`` float32 for window ``win``.

    The in-scan variant of ``traffic.ref.sample_arrival_bits_ref``: one
    window at a time, with the window index *traced* (it is the scan
    counter ``k >> 6``) instead of static. Same draws, same integer
    count and burst-length thresholds (``burst_thr`` from
    ``traffic.ref.geometric_tables``) — the produced stream is
    bit-identical to every host backend (tested).
    """
    keys = jnp.asarray(keys, jnp.uint32)
    thresholds = jnp.asarray(thresholds, jnp.int32)
    R = keys.shape[0]
    c0 = jnp.asarray(win, jnp.uint32)                    # window counter
    c1 = jnp.arange(n_onus, dtype=jnp.uint32)[None, :]
    k0 = keys[:, 0][:, None]
    k1 = keys[:, 1][:, None]

    # window burst count: integer inverse CDF, k = #{ j : bits > T_j }
    kd0, kd1 = draw_key(k0, k1, 0)
    w0, _ = threefry2x32_ref(kd0, kd1, c0, c1)           # (R, N)
    b24 = (w0 >> jnp.uint32(8)).astype(jnp.int32)
    count = (b24[:, None, :] > thresholds[:, :, None]).astype(
        jnp.int32).sum(axis=1)                            # (R, N)

    # bursts: draw j of every (row, onu) stream is an independent
    # threefry instance (Weyl key), so the j axis vectorises.  Per-cycle
    # packet totals are small integers — exactly representable in
    # float32 — so unordered scatter-adds produce the same bits as the
    # sequential per-draw accumulation they replace.  ``n_draws`` is a
    # Poisson tail bound ~2x the realised maximum count, so the second
    # half of the draws is usually all-dead: it is scattered (and its
    # threefry evaluated) only under a ``lax.cond`` — adding nothing is
    # bitwise adding zeros, so the skip is exact.
    def _scatter(buf, j0: int, j1: int):
        j = jnp.arange(j0 + 1, j1 + 1, dtype=jnp.uint32)[None, :, None]
        bd0, bd1 = draw_key(k0[:, None, :], k1[:, None, :], j)
        x0, x1 = threefry2x32_ref(bd0, bd1, c0, c1[None])  # (R, j, N)
        place = (x0 >> jnp.uint32(32 - _WIN_SHIFT)).astype(jnp.int32)
        glen = burst_length(x1, burst_thr)
        live = j.astype(jnp.int32) <= count[:, None, :]
        return buf.at[
            jnp.arange(R)[:, None, None], place,
            jnp.arange(n_onus)[None, None, :],
        ].add(jnp.where(live, glen, jnp.float32(0.0)))

    j_half = max(1, n_draws // 2)
    packets = _scatter(
        jnp.zeros((R, WINDOW, n_onus), jnp.float32), 0, j_half)
    if j_half < n_draws:
        packets = lax.cond(
            count.max() > j_half,
            lambda p: _scatter(p, j_half, n_draws),
            lambda p: p, packets)
    return packets * jnp.asarray(packet_bits, jnp.float32)


def waterfill_grants_xla(backlog, hol, cap):
    """Standalone jitted entry for the oracle waterfill (parity tests
    call this directly; the scan program inlines the ref body)."""
    return jax.jit(waterfill_grants_ref)(backlog, hol, cap)
