"""Pallas TPU kernel for the waterfill grant step.

The grant step of the cycle engine serves queues oldest-first until the
cycle capacity runs out (``repro.net.engine._waterfill``).  The numpy /
XLA oracles express it as a stable argsort + prefix sum; on TPU a sort
per cycle is the wrong shape (tiny rows, huge batch), so this kernel
uses the O(N^2) *rank-sum* form instead:

    S_i   = sum_j backlog_j * [key_j < key_i  or  (key_j == key_i and j < i)]
    room  = cap - S_i
    g_i   = min(backlog_i, room)   if room > eps else 0

``S_i`` is exactly the sorted-prefix "water already poured" for queue
``i`` under a *stable* oldest-first order, so the grants match the sort
formulation (up to f32 accumulation order).  The comparison matrix is a
natural VPU shape: a (BJ, BI) mask — contributing queues ``j`` on
sublanes, granted queues ``i`` on lanes — reduced over sublanes, streamed
over j-tiles with a fori accumulator: no sort, no scatter.

Layout for the TPU tiling: a grid cell covers ``ROWS`` (8) rows by a
128-lane tile of queues.  The j-side operands arrive pre-transposed as
``(R / 8, N, 8)`` (queues on sublanes, the cell's 8 rows on lanes), so a
row's contributing column is a plain ref slice and the kernel needs no
in-register transpose; ``cap`` rides as an ``(R, 1)`` column block.
Every index and loop bound is explicitly int32 — the kernel is traced
inside the engine's scoped x64 context, and Mosaic takes no 64-bit type.

Full drains must stay *exact* (the serve step detects them by float
equality), so callers recover them from ``g == backlog`` in f32 — when
``room >= backlog`` the kernel emits bitwise ``backlog`` — and restore
the f64 backlog for those lanes (``ops._waterfill_device``).

On TPU the engine dispatches it compiled; the CPU test suite runs it
through ``interpret=True`` (tests/test_ponsim_jit.py) and compiles it
for a described v5e (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

CAP_EPS = 1e-9   # repro.net.engine.CAP_EPS

ROWS = 8         # rows per grid cell (f32 sublane tile)
BLOCK_I = 128    # queues granted per grid cell (lane width)
BLOCK_J = 128    # contribution tile streamed per fori step
_I0 = np.int32(0)  # block index literal that stays int32 under x64


def _waterfill_kernel(b_ref, key_ref, cap_ref, bt_ref, kt_ref, g_ref, *,
                      n_cols: int):
    i = pl.program_id(1)
    idx_i = i * BLOCK_I + lax.broadcasted_iota(jnp.int32, (1, BLOCK_I), 1)
    n_tiles = jnp.int32(n_cols // BLOCK_J)
    for r in range(ROWS):
        ki = key_ref[r:r + 1, :]                  # (1, BI) granted keys

        def body(jc, acc, r=r, ki=ki):
            j0 = pl.multiple_of(jc * BLOCK_J, BLOCK_J)
            bj = bt_ref[0, pl.ds(j0, BLOCK_J), r:r + 1]     # (BJ, 1)
            kj = kt_ref[0, pl.ds(j0, BLOCK_J), r:r + 1]
            idx_j = j0 + lax.broadcasted_iota(jnp.int32, (BLOCK_J, 1), 0)
            earlier = (kj < ki) | ((kj == ki) & (idx_j < idx_i))
            return acc + jnp.sum(
                jnp.where(earlier, bj, jnp.float32(0.0)), axis=0,
                keepdims=True)

        served = lax.fori_loop(
            jnp.int32(0), n_tiles, body,
            jnp.zeros((1, BLOCK_I), jnp.float32))
        room = cap_ref[r:r + 1, :] - served
        g_ref[r:r + 1, :] = jnp.where(
            room > jnp.float32(CAP_EPS),
            jnp.minimum(b_ref[r:r + 1, :], room), jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def waterfill_grants_pallas(backlog, key, cap, *, interpret: bool = False):
    """Rank-sum waterfill grants, float32.

    backlog: (R, N) f32, key: (R, N) f32 (lower = older; +inf = empty),
    cap: (R,) f32.  N must be a multiple of 128 — pad with
    ``backlog=0, key=+inf`` (a zero-backlog queue contributes nothing
    and takes nothing).  Rows are padded to a multiple of 8 here.
    Returns (R, N) f32 grants; full drains are bitwise ``backlog``.
    """
    r, n = backlog.shape
    if n % BLOCK_I:
        raise ValueError(f"n_queues {n} not a multiple of {BLOCK_I}")
    rp = -(-r // ROWS) * ROWS
    if rp != r:
        backlog = jnp.pad(backlog, ((0, rp - r), (0, 0)))
        key = jnp.pad(key, ((0, rp - r), (0, 0)),
                      constant_values=jnp.float32(jnp.inf))
        cap = jnp.pad(cap, (0, rp - r))

    def _cols(x):                                 # (R, N) -> (R/8, N, 8)
        return x.reshape(rp // ROWS, ROWS, n).transpose(0, 2, 1)

    g = pl.pallas_call(
        functools.partial(_waterfill_kernel, n_cols=n),
        grid=(rp // ROWS, n // BLOCK_I),
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK_I), lambda a, b: (a, b)),
            pl.BlockSpec((ROWS, BLOCK_I), lambda a, b: (a, b)),
            pl.BlockSpec((ROWS, 1), lambda a, b: (a, _I0)),
            pl.BlockSpec((1, n, ROWS), lambda a, b: (a, _I0, _I0)),
            pl.BlockSpec((1, n, ROWS), lambda a, b: (a, _I0, _I0)),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK_I), lambda a, b: (a, b)),
        out_shape=jax.ShapeDtypeStruct((rp, n), jnp.float32),
        interpret=interpret,
    )(backlog, key, cap.reshape(rp, 1), _cols(backlog), _cols(key))
    return g[:r]
