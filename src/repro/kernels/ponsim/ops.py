"""jit backend for the batched PON cycle engine (`backend="jit"`).

``run_phase_device`` compiles one entire transfer phase — every cycle of
every ``(case, pon)`` row — into a single ``lax.while_loop`` device
program: deadline/outage capacity masking, the fused counter-based
traffic sampler (arrival bits are generated on-device in 64-cycle
windows and never touch the host), background FIFO push/serve over a
prefix-sum ring, the stable-argsort waterfill grants (Pallas rank-sum
kernel on TPU, the jnp oracle elsewhere), the CPS max-min split, FL
queue serves and completion credit.  The numpy engine
(``repro.net.engine._run_phase``) is the parity oracle at rtol 1e-6.

Carry layout (all fixed-shape; ``R`` rows, ``U`` client columns, ``N``
ONUs, ``Wr = HISTORY_CYCLES``):

======================  =======================  =======================
carry                   shape/dtype              numpy counterpart
======================  =======================  =======================
``k, t``                i32 / f64 scalars        cycle index, clock
``rem/done/done_t``     (R, U) f64/bool/f64      ``_run_phase`` locals
``waiting``             (R, U) bool              un-pushed clients
``qb/push_key/…time``   (R, U) f64/i64/f64       ``_FLQueues``
``buf``                 (R, 64, N) f32           sampler window cache
``cum/drained/backlog`` (R, N) f64               ``_BgQueues`` prefixes
``ptr``                 (R, N) i32               bg head-of-line cycle
``ring``                (R, N, Wr) f64           last-Wr cycle prefixes
``exact``               bool scalar              ring-walk validity
======================  =======================  =======================

The one structure that cannot be carried whole on device is the bg
queues' unbounded prefix *history*: the numpy engine walks it to find
the new head after a partial drain.  Per cycle at most ONE queue per
row is partially granted (the waterfill pours whole backlogs until the
marginal queue), and its head almost always sits within the last few
cycles — so the carry keeps a ``Wr``-cycle prefix ring and the serve
step walks that.  A marginal queue whose head has aged out of the ring
(sustained overload) clears the ``exact`` flag; the host entry then
returns ``None`` and the engine re-runs that phase on the numpy path,
so the backend is *always* exact, merely slower in regimes the device
program was not sized for.  Each such re-run is counted in
:func:`host_fallbacks` (``"ring_overflow"``).

Multi-tenant sweeps (``SweepCase.jobs``, PR 9) are NOT compiled: the
per-cycle inter-job fairness split (``repro.net.jobs.job_fair_split``)
and the per-job prefix spending would add a ragged job axis to every
carry above.  The engine runs multi-job sweeps on the numpy path and
counts each in :func:`host_fallbacks` (``"multi_job"``, DESIGN.md §12); a
degenerate all-single-job sweep normalises to the plain layout and
keeps this backend.

Precision policy: queue state is float64, so the program is built and
called under a scoped ``jax.enable_x64(True)`` context — the
global x64 flag is never flipped for library users (regression-tested).
The TPU has no native float64: XLA emulates it, and every op on this
path compiles in seconds except ``cumsum``, whose float64 reduce-window
form the TPU compiler does not finish, so prefix sums go through
``ref.prefix_sum`` (a parallel scan).
The fused sampler keeps the traffic kernels' explicit uint32/float32
dtypes, which is what makes its stream bit-identical to the host
backends.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.ponsim import ref as _ref
from repro.kernels.ponsim.kernel import waterfill_grants_pallas
from repro.kernels.traffic.ref import _WIN_SHIFT, WINDOW

CAP_EPS = 1e-9                       # repro.net.engine constants
SEG_EPS = 1.0
EPS_BITS = 1.0
_IKEY_INF = np.int64(np.iinfo(np.int64).max // 4)

HISTORY_CYCLES = 128                 # bg prefix ring length (pow2)

# program cache: one compilation per (mode, shapes, flags, layout)
_programs: dict = {}
_COMPILE_COUNT = 0                   # bumped at trace time (tested)
_PALLAS_INTERPRET = False            # tests flip to run the kernel on CPU
# phases (or whole sweeps) that asked for the device and ran on the host
# numpy engine instead: "ring_overflow" (the bg ring walk lost
# exactness) and "multi_job" (the carry has no job axis)
_HOST_FALLBACKS = {"ring_overflow": 0, "multi_job": 0}


def compile_count() -> int:
    return _COMPILE_COUNT


def host_fallbacks() -> dict:
    """Per-reason count of device requests the host engine served."""
    return dict(_HOST_FALLBACKS)


def note_host_fallback(reason: str) -> None:
    _HOST_FALLBACKS[reason] += 1


def clear_cache() -> None:
    _programs.clear()


def _waterfill_device(backlog, hol, cap, use_pallas: bool):
    """Waterfill grants with exact full drains.

    The oracle path is bitwise-faithful to ``engine._waterfill``.  The
    Pallas path runs in f32; full drains come back as bitwise ``b32``
    (kernel contract), so the f64 backlog is restored for those lanes —
    the serve step's ``grants == backlog`` fast path stays exact."""
    if not use_pallas:
        return _ref.waterfill_grants_ref(backlog, hol, cap)
    n = backlog.shape[1]
    pad = (-n) % 128
    b32 = backlog.astype(jnp.float32)
    k32 = hol.astype(jnp.float32)
    if pad:
        b32 = jnp.pad(b32, ((0, 0), (0, pad)))
        k32 = jnp.pad(k32, ((0, 0), (0, pad)),
                      constant_values=jnp.float32(jnp.inf))
    g32 = waterfill_grants_pallas(b32, k32, cap.astype(jnp.float32),
                                  interpret=_PALLAS_INTERPRET)[:, :n]
    b32 = b32[:, :n]
    fullm = (g32 == b32) & (b32 > 0)
    return jnp.where(fullm, backlog, g32.astype(backlog.dtype))


def _build_program(spec, lay, n_draws: int):
    """Close the static layout/config into one jitted phase program."""
    global _COMPILE_COUNT
    (mode, R, U, N, S, P, has_bg, has_cps, has_deadline, has_outage,
     fill_unfinished, use_pallas, max_slots, _ndraws, _nlevels,
     _onu_sig) = spec
    single, identity = lay.single, lay.identity
    fast = mode == "fcfs" and single
    lay_onu = np.asarray(lay.onu, np.int64)              # (U,)
    lay_pos = np.arange(U, dtype=np.int64)
    seg_starts = np.asarray(lay.seg_starts, np.int64)
    seg_onus = np.asarray(lay.seg_onus, np.int64)
    Sg = len(seg_starts)
    seg_ids = np.repeat(np.arange(Sg), np.asarray(lay.seg_len))
    Wr = HISTORY_CYCLES

    def _backlog_per_onu(qb):
        if identity:
            return qb
        if single:
            return jnp.zeros((R, N), qb.dtype).at[:, seg_onus].set(qb)
        seg = jax.ops.segment_sum(qb.T, seg_ids, num_segments=Sg,
                                  indices_are_sorted=True).T
        return jnp.zeros((R, N), qb.dtype).at[:, seg_onus].set(seg)

    def _heads(qb, push_key):
        nonzero = qb > 0.0
        pk = jnp.where(nonzero, push_key, 0)
        combined = jnp.where(nonzero, pk * np.int64(U) + lay_pos,
                             _IKEY_INF)
        m = jax.ops.segment_min(combined.T, seg_ids, num_segments=Sg,
                                indices_are_sorted=True).T      # (R, Sg)
        has = m < _IKEY_INF
        pos = jnp.where(has, m % np.int64(U), 0)
        return has, pos

    def _hol_per_onu(qb, push_key, push_time):
        if identity:
            return jnp.where(qb > 0.0, push_time, jnp.inf)
        if single:
            return jnp.full((R, N), jnp.inf,
                            push_time.dtype).at[:, seg_onus].set(
                jnp.where(qb > 0.0, push_time, jnp.inf))
        has, pos = _heads(qb, push_key)
        times = jnp.where(has,
                          jnp.take_along_axis(push_time, pos, axis=1),
                          jnp.inf)
        return jnp.full((R, N), jnp.inf,
                        push_time.dtype).at[:, seg_onus].set(times)

    def _count_le(a, v):
        """Per-row ``#{j : a[r, j] <= v[r]}`` for row-sorted ``a``."""
        return jax.vmap(
            lambda ar, vr: jnp.searchsorted(ar, vr, side="right")
        )(a, v).astype(jnp.int32)

    def _first_ge(a, v):
        """Per-row index of the first ``a[r, j] >= v[r]``."""
        return jax.vmap(
            lambda ar, vr: jnp.searchsorted(ar, vr, side="left")
        )(a, v).astype(jnp.int32)

    def program(dyn):
        global _COMPILE_COUNT
        _COMPILE_COUNT += 1                 # trace-time side effect
        cyc, prop = dyn["cyc"], dyn["prop"]
        tmax = dyn["tmax"]
        part = dyn["part"]
        rem0 = dyn["rem0"]
        f64 = rem0.dtype

        def _slot_grants(backlog_onu, t, cap):
            te_g = dyn["te"] + cyc
            active = dyn["svalid"] & (dyn["ts"] < t + cyc) & (te_g > t)
            overlap = (jnp.minimum(te_g, t + cyc)
                       - jnp.maximum(dyn["ts"], t))
            want = dyn["srate"] * jnp.maximum(overlap, 0.0)
            want = jnp.minimum(
                want, jnp.take_along_axis(backlog_onu, dyn["sonu"], 1))
            want = jnp.where(active & (want > 0.0), want, 0.0)
            prefix = _ref.prefix_sum(want)
            grants = jnp.minimum(
                want, jnp.maximum(cap[:, None] - (prefix - want), 0.0))
            rows = jnp.broadcast_to(jnp.arange(R)[:, None], (R, S))
            return jnp.zeros((R, N), f64).at[rows, dyn["sonu"]].add(
                grants)

        done0 = ~part | (rem0 <= 0.0)
        carry = {
            "k": jnp.int32(0),
            "t": jnp.zeros((), f64),
            "done_t": jnp.full((R, U), jnp.nan, f64),
            "exact": jnp.bool_(True),
        }
        if fast:
            # Scalar-S carry: with single-client queues served in a
            # priority order known before the loop (host tables), the
            # whole FL queue system collapses to one cumulative-service
            # scalar per row plus the count of completed ranks.
            carry.update(
                fls=jnp.zeros((R,), f64),
                cdone=jnp.zeros((R,), jnp.int32),
            )
        else:
            carry.update(
                rem=rem0,
                done=done0,
                waiting=part & ~done0,
                qb=jnp.zeros((R, U), f64),
                push_key=jnp.full((R, U), _IKEY_INF, jnp.int64),
                push_time=jnp.zeros((R, U), f64),
            )
        if has_bg:
            carry.update(
                buf=jnp.zeros((R, WINDOW, N), jnp.float32),
                cum=jnp.zeros((R, N), f64),
                drained=jnp.zeros((R, N), f64),
                backlog=jnp.zeros((R, N), f64),
                ptr=jnp.zeros((R, N), jnp.int32),
                ring=jnp.zeros((R, N, Wr), f64),
            )

        def cond(c):
            if fast:
                liv = dyn["m_live"] > c["cdone"]
                ok = ((c["t"] < tmax) & liv.any()
                      & (c["k"] < dyn["k_max"]))
                if has_deadline:
                    ok &= (liv & (dyn["cap_t"] > c["t"])).any()
                return ok
            live = ~c["done"] & part
            ok = (c["t"] < tmax) & live.any() & (c["k"] < dyn["k_max"])
            if has_deadline:
                # the numpy loop breaks at the body top, before any
                # mutation, when no live client's row deadline is ahead
                ok &= (live & (dyn["cap_t"] > c["t"])[:, None]).any()
            return ok

        def body(c):
            k, t = c["k"], c["t"]
            out = dict(c)
            cap_cyc = dyn["cap_col"]
            if has_deadline:
                cap_cyc = jnp.where(dyn["cap_t"] > t, cap_cyc, 0.0)
            if has_outage:
                dark = (dyn["out0"] <= t) & (t < dyn["out1"])
                cap_cyc = jnp.where(dark, 0.0, cap_cyc)

            # ---- bg arrivals: fused threefry sampler + FIFO push
            if has_bg:
                buf = lax.cond(
                    (k & (WINDOW - 1)) == 0,
                    lambda _: _ref.sample_window_ref(
                        dyn["keys"], dyn["thr"], k >> _WIN_SHIFT,
                        n_onus=N, n_draws=n_draws,
                        burst_thr=dyn["burst_thr"],
                        packet_bits=dyn["packet_bits"]),
                    lambda _: c["buf"], None)
                bits = lax.dynamic_index_in_dim(
                    buf, k & (WINDOW - 1), axis=1,
                    keepdims=False).astype(f64)
                fresh = (c["backlog"] <= 0.0) & (bits > 0.0)
                cum = c["cum"] + bits
                bg_backlog = cum - c["drained"]
                bg_ptr = jnp.where(fresh, k, c["ptr"])
                ring = lax.dynamic_update_slice(
                    c["ring"], cum[:, :, None],
                    (jnp.int32(0), jnp.int32(0), k & (Wr - 1)))
                out.update(buf=buf, cum=cum, backlog=bg_backlog,
                           ptr=bg_ptr, ring=ring)

            # ---- FL push
            if fast:
                # pushes are a host-precomputed prefix of the rank
                # order: the pushed-total boundary T_k replaces all
                # per-client push bookkeeping
                npk = _count_le(dyn["kp_rank"],
                                jnp.broadcast_to(k, (R,)))
                t_k = jnp.take_along_axis(
                    dyn["p_incl"], npk.astype(jnp.int64)[:, None],
                    axis=1)[:, 0]
                fl_tot = t_k - c["fls"]
            else:
                newly = c["waiting"] & (dyn["ready"] <= t + cyc)
                qb = jnp.where(newly, c["rem"], c["qb"])
                push_key = jnp.where(
                    newly,
                    k.astype(jnp.int64) * np.int64(U + 1)
                    + dyn["list_pos"],
                    c["push_key"])
                push_time = jnp.where(
                    newly, jnp.maximum(dyn["ready"], t),
                    c["push_time"])
                out.update(waiting=c["waiting"] & ~newly,
                           push_key=push_key, push_time=push_time)

            # ---- grants
            backlog_onu = None if fast else _backlog_per_onu(qb)
            if mode == "fcfs":
                bg_sum = (out["backlog"].sum(axis=1) if has_bg else 0.0)
                if has_cps:
                    fl_want = (fl_tot if fast
                               else backlog_onu.sum(axis=1))
                    want = jnp.minimum(bg_sum + fl_want, cap_cyc)
                    eff = _ref.cps_waterfill_ref(
                        want.reshape(-1, P), dyn["cps_cap"]).reshape(-1)
                else:
                    eff = cap_cyc
                if has_bg:
                    # the numpy `_waterfill` lazy hard-row check, hoisted
                    # to a scalar cond: when every row's demand sits at
                    # least one bit under capacity the pour grants full
                    # backlogs regardless of age order, so ordering work
                    # is skipped entirely.  Under sub-unit load that is
                    # the common cycle; only bursts take the hard branch.
                    easy = jnp.all(bg_sum <= eff - 1.0)

                    def _bg_easy(b, ptr, e):
                        return b, jnp.bool_(False)

                    if use_pallas:
                        def _bg_hard(b, ptr, e):
                            hol = jnp.where(b > 0.0, ptr.astype(f64),
                                            jnp.inf)
                            return (_waterfill_device(b, hol, e, True),
                                    jnp.bool_(False))
                    else:
                        def _bg_hard(b, ptr, e):
                            # bg head-of-line keys are arrival *cycles*,
                            # so the stable argsort collapses to a
                            # counting pour over `Wr` age buckets:
                            # bucket-sum scatter + tiny suffix sums +
                            # one column prefix for the single marginal
                            # bucket — O(N) instead of O(N log N), and
                            # ~20x cheaper than XLA's sort here.  Ages
                            # clip at Wr-1; if the margin lands in that
                            # clipped bucket with 2+ queues their column
                            # order may differ from true arrival order,
                            # so that (sustained-overload) case clears
                            # `exact` and the host re-runs on numpy.
                            has = b > 0.0
                            age = jnp.clip(k - ptr, 0, Wr - 1)
                            aidx = jnp.where(has, age, 0)
                            bval = jnp.where(has, b, 0.0)
                            rws = jnp.arange(R)[:, None]
                            bs = jnp.zeros((R, Wr), b.dtype).at[
                                rws, aidx].add(bval)
                            flip = _ref.prefix_sum(bs[:, ::-1])
                            csame = flip[:, ::-1]          # Σ age ≥ a
                            colder = csame - bs            # Σ age > a
                            tq = jnp.take_along_axis(colder, aidx, 1)
                            cq = jnp.take_along_axis(csame, aidx, 1)
                            capq = e[:, None]
                            fullq = has & (cq <= capq)
                            marg = has & (tq < capq) & (cq > capq)
                            bm = jnp.where(marg, bval, 0.0)
                            wq = _ref.prefix_sum(bm) - bm
                            room = capq - (tq + wq)
                            pour = jnp.where(room > CAP_EPS,
                                             jnp.minimum(b, room), 0.0)
                            g = jnp.where(fullq, b,
                                          jnp.where(marg, pour, 0.0))
                            nclip = (has & (age == Wr - 1)).sum(axis=1)
                            amb = ((marg & (aidx == Wr - 1)).any(axis=1)
                                   & (nclip >= 2)).any()
                            return g, amb
                    bg_grants, bg_amb = lax.cond(
                        easy, _bg_easy, _bg_hard,
                        out["backlog"], out["ptr"], eff)
                    out["exact"] = out["exact"] & ~bg_amb
                    cap_fl = eff - bg_grants.sum(axis=1)
                else:
                    cap_fl = eff
                if not fast:
                    fl_grants = _waterfill_device(
                        backlog_onu,
                        _hol_per_onu(qb, push_key, push_time),
                        cap_fl, use_pallas)
            else:
                fl_grants = _slot_grants(backlog_onu, t, cap_cyc)
                if has_cps:
                    # recompute with the waterfilled shares is a bitwise
                    # no-op for rows the CPS does not cut (the numpy
                    # path's conditional recompute, branch-free)
                    want = fl_grants.sum(axis=1)
                    eff = _ref.cps_waterfill_ref(
                        want.reshape(-1, P), dyn["cps_cap"]).reshape(-1)
                    fl_grants = _slot_grants(backlog_onu, t, eff)

            # ---- bg serve: full drains + the one marginal queue/row
            if has_bg:
                cum, drained = out["cum"], out["drained"]
                backlog, ptr = out["backlog"], out["ptr"]
                full = (bg_grants > 0.0) & (bg_grants == backlog)
                budget = jnp.where(full, 0.0, bg_grants)
                drained = jnp.where(full, cum, drained)
                backlog = jnp.where(full, 0.0, backlog)
                ptr = jnp.where(full, k + 1, ptr)
                part_q = budget > CAP_EPS
                has_part = part_q.any(axis=1)
                jm = jnp.argmax(part_q, axis=1)     # ≤1 partial per row
                rows = jnp.arange(R)
                tgt = drained[rows, jm] + budget[rows, jm]
                cum_q = cum[rows, jm]
                # prefix values of the marginal queue over the last Wr
                # cycles, ascending (pre-history ring slots hold 0 and
                # never exceed a positive target)
                cyc_idx = jnp.arange(Wr, dtype=jnp.int32) - (Wr - 1) + k
                pref = jnp.take(out["ring"][rows, jm],
                                cyc_idx & (Wr - 1), axis=1)
                ex1 = pref > tgt[:, None]
                j1rel = jnp.argmax(ex1, axis=1).astype(jnp.int32)
                jstar = k - (Wr - 1) + j1rel
                seg_end = jnp.take_along_axis(
                    pref, j1rel[:, None], 1)[:, 0]
                snap = seg_end - tgt <= SEG_EPS
                dr1 = jnp.where(snap, seg_end, tgt)
                bklg = cum_q - dr1
                low = bklg < 0.5
                # snap consumed through jstar; next head = first later
                # cycle whose prefix exceeds the snapped drain (always
                # in-window: prefix(k) = cum > drained when not low)
                ex2 = ((pref > dr1[:, None])
                       & (jnp.arange(Wr)[None, :] > j1rel[:, None]))
                j2 = k - (Wr - 1) + jnp.argmax(ex2, axis=1).astype(
                    jnp.int32)
                new_dr = jnp.where(low, cum_q, dr1)
                new_bk = jnp.where(low, 0.0, bklg)
                new_pt = jnp.where(low, k + 1,
                                   jnp.where(snap, j2, jstar))
                # the walk is exact unless the head had already aged out
                # of the ring AND the window start exceeds the target
                stale = has_part & ex1[:, 0] & (
                    ptr[rows, jm] < k - (Wr - 1))
                drained = drained.at[rows, jm].set(
                    jnp.where(has_part, new_dr, drained[rows, jm]))
                backlog = backlog.at[rows, jm].set(
                    jnp.where(has_part, new_bk, backlog[rows, jm]))
                ptr = ptr.at[rows, jm].set(
                    jnp.where(has_part, new_pt, ptr[rows, jm]))
                out.update(cum=cum, drained=drained, backlog=backlog,
                           ptr=ptr,
                           exact=out["exact"] & ~stale.any())

            # ---- FL serve + completion credit
            if fast:
                # Single-client queues keep their (push_time, column)
                # key for the whole phase and pushes are ready-driven,
                # so service is strictly prefix-contiguous in a
                # priority order known before the loop: the waterfill
                # pour over all queues reduces to advancing one
                # cumulative-service scalar S per row against the
                # host-precomputed demand boundaries Q_r.  A client's
                # sub-SEG_EPS residual is discarded on its last serve
                # (the numpy drop), which is exactly "snap S to the
                # next boundary when it lands within SEG_EPS below it"
                # — the drop and the EPS_BITS credit share the same
                # threshold, so rank r is complete iff Q_r <= S.
                s_pre = c["fls"]
                capx = jnp.maximum(cap_fl, 0.0)
                s1 = jnp.where(
                    cap_fl > CAP_EPS,
                    jnp.where(fl_tot <= capx, t_k, s_pre + capx),
                    s_pre)
                qpad = jnp.concatenate(
                    [dyn["q_bound"], jnp.full((R, 1), jnp.inf, f64)],
                    axis=1)
                rkx = _first_ge(dyn["q_bound"], s1)
                qv = jnp.take_along_axis(
                    qpad, rkx.astype(jnp.int64)[:, None], axis=1)[:, 0]
                bump = (s1 > s_pre) & (qv - s1 <= SEG_EPS)
                s2 = jnp.where(bump, qv, s1)
                c_new = _count_le(dyn["q_bound"], s2)
                c_old = c["cdone"]

                def _credit(dt):
                    hit = ((dyn["rank_u"] >= c_old[:, None])
                           & (dyn["rank_u"] < c_new[:, None]))
                    return jnp.where(hit, t + cyc + prop, dt)

                out.update(
                    fls=s2,
                    cdone=c_new,
                    done_t=lax.cond((c_new > c_old).any(), _credit,
                                    lambda dt: dt, c["done_t"]),
                    k=k + 1,
                    t=t + cyc,
                )
                return out
            if single:
                fl_budget = (fl_grants if identity
                             else fl_grants[:, lay_onu])
                act = (fl_budget > CAP_EPS) & (qb > 0.0)
                take = jnp.where(act, jnp.minimum(fl_budget, qb), 0.0)
                drop = act & (qb - take <= SEG_EPS)
                qb2 = jnp.where(drop, 0.0, qb - take)
            else:
                fullf = (fl_grants > 0.0) & (fl_grants == backlog_onu)
                qb1 = jnp.where(fullf[:, lay_onu], 0.0, qb)
                budget0 = jnp.where(fullf, 0.0, fl_grants)[:, seg_onus]
                rows2 = jnp.arange(R)[:, None]

                def serve_it(_, st):
                    qb_c, budget_c = st
                    has, pos = _heads(qb_c, push_key)
                    srv = has & (budget_c > CAP_EPS)
                    hq = jnp.take_along_axis(qb_c, pos, axis=1)
                    take = jnp.where(srv, jnp.minimum(budget_c, hq),
                                     0.0)
                    resid = jnp.where(srv, hq - take, jnp.inf)
                    drop = srv & (resid <= SEG_EPS)
                    newq = jnp.where(drop, 0.0, hq - take)
                    # scatter through a scratch column: non-served
                    # segments park at index U instead of clobbering
                    # column 0
                    qb_ext = jnp.concatenate(
                        [qb_c, jnp.zeros((R, 1), f64)], axis=1)
                    qb_ext = qb_ext.at[
                        rows2, jnp.where(srv, pos, U)].set(
                        jnp.where(srv, newq, 0.0))
                    charge = jnp.where(drop, resid, 0.0)
                    return (qb_ext[:, :U],
                            jnp.maximum(budget_c - take - charge, 0.0))

                qb2, _ = lax.fori_loop(0, max_slots, serve_it,
                                       (qb1, budget0))
            drained_fl = qb - qb2
            new_rem = c["rem"] - drained_fl
            newly_done = (~c["done"] & (drained_fl > 0.0)
                          & (new_rem <= EPS_BITS))
            out.update(
                qb=qb2,
                rem=jnp.where(newly_done, 0.0,
                              jnp.maximum(new_rem, 0.0)),
                done=c["done"] | newly_done,
                done_t=jnp.where(newly_done, t + cyc + prop,
                                 c["done_t"]),
                k=k + 1,
                t=t + cyc,
            )
            return out

        final = lax.while_loop(cond, body, carry)
        done_t, t = final["done_t"], final["t"]
        if fast:
            # reconstruct per-client rem/done from the final S against
            # each column's demand boundary (done clients land at
            # exactly 0.0, untouched queues at exactly rem0)
            scol = final["fls"][:, None]
            served_done = dyn["pushes"] & (dyn["q_col"] <= scol)
            done_f = done0 | served_done
            rem_f = jnp.where(
                dyn["pushes"],
                jnp.clip(dyn["q_col"] - scol, 0.0, rem0), rem0)
        else:
            done_f = final["done"]
            rem_f = final["rem"]
        if has_deadline:
            left = part & ~done_f & ~dyn["finite_dl"][:, None]
            done_t = jnp.where(left, t + prop, done_t)
        elif fill_unfinished:
            left = part & ~done_f
            done_t = jnp.where(left, t + prop, done_t)
        return done_t, rem_f, final["exact"]

    return jax.jit(program)


def _phase_inputs(cfg, lay, rem_init, ready_t, mode: str, *,
                  keys=None, lams=None, slot_arrays=None,
                  max_t: float = 600.0, fill_unfinished: bool = True,
                  cap_row=None, cps_cap: Optional[float] = None,
                  n_pons: int = 1, deadline_row=None,
                  outage_row=None, use_pallas: Optional[bool] = None):
    """Host tables of one phase: ``(spec, dyn, n_draws)``, where
    ``spec`` keys the program cache and ``dyn`` holds its inputs."""
    R, U = rem_init.shape
    N = int(cfg.n_onus)
    cyc = float(cfg.cycle_time_s)
    prop = float(cfg.propagation_s)
    if cap_row is None:
        cap_row = np.full(
            (R,), cfg.line_rate_bps * cyc * cfg.efficiency)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    has_deadline = deadline_row is not None
    has_outage = outage_row is not None
    has_cps = cps_cap is not None
    if has_deadline:
        cap_t = np.where(np.isfinite(deadline_row), deadline_row, max_t)
        tmax = float(cap_t.max())
    else:
        tmax = float(max_t)
    k_max = int(np.ceil(max(tmax, 0.0) / cyc)) + 16

    use_bg = mode == "fcfs"
    lams = (np.zeros((R,), np.float32) if lams is None
            else np.asarray(lams, np.float32))
    has_bg = bool(use_bg and lams.size and float(lams.max()) > 0.0)
    n_draws = 0
    dyn = {
        "cyc": np.float64(cyc),
        "prop": np.float64(prop),
        "tmax": np.float64(tmax),
        "k_max": np.int32(k_max),
        "part": np.asarray(lay.part, bool),
        "rem0": np.asarray(rem_init, np.float64),
        "ready": np.asarray(ready_t, np.float64),
        "list_pos": np.asarray(lay.list_pos, np.int64),
        "cap_col": np.asarray(cap_row, np.float64),
    }
    if mode == "fcfs" and lay.single:
        # Scalar-S tables (see the grant/serve step): push cycles and
        # push times are ready-driven, so replay the loop's exact float
        # accumulation of t on the host, sort the priority order once,
        # and hand the program cumulative-demand boundaries per rank.
        t_seq = np.empty(k_max, np.float64)
        t_seq[0] = 0.0
        if k_max > 1:
            np.cumsum(np.full(k_max - 1, cyc), out=t_seq[1:])
        tc = t_seq + cyc                    # the loop's t + cyc values
        ready = np.asarray(ready_t, np.float64)
        kp = np.searchsorted(tc, ready.ravel()).reshape(R, U)
        part_b = np.asarray(lay.part, bool)
        rem_b = np.asarray(rem_init, np.float64)
        pushes = part_b & (rem_b > 0.0) & (kp < k_max)
        pt = np.where(
            pushes,
            np.maximum(ready, t_seq[np.minimum(kp, k_max - 1)]),
            np.inf)
        # rank order = the waterfill's stable sort over per-ONU push
        # times: primary key push time, ties broken by ONU index
        onu_key = np.broadcast_to(
            np.asarray(lay.onu, np.int64), (R, U))
        rk = np.lexsort((onu_key, pt), axis=1)          # rank -> col
        rows_ = np.arange(R)[:, None]
        m_rank = np.where(pushes, rem_b, 0.0)[rows_, rk]
        p_incl = np.zeros((R, U + 1))
        np.cumsum(m_rank, axis=1, out=p_incl[:, 1:])
        push_rank = pushes[rows_, rk]
        q_bound = np.where(push_rank, p_incl[:, 1:], np.inf)
        rank_u = np.argsort(rk, axis=1)                 # col -> rank
        dyn["kp_rank"] = np.where(
            push_rank, kp[rows_, rk], k_max).astype(np.int32)
        dyn["p_incl"] = p_incl
        dyn["q_bound"] = q_bound
        dyn["rank_u"] = rank_u.astype(np.int32)
        dyn["q_col"] = q_bound[rows_, rank_u]
        dyn["pushes"] = pushes
        dyn["m_live"] = (part_b & (rem_b > 0.0)).sum(
            axis=1).astype(np.int32)
    if has_deadline:
        dyn["cap_t"] = np.asarray(cap_t, np.float64)
        dyn["finite_dl"] = np.isfinite(deadline_row)
    if has_outage:
        dyn["out0"] = np.asarray(outage_row[:, 0], np.float64)
        dyn["out1"] = np.asarray(outage_row[:, 1], np.float64)
    if has_cps:
        dyn["cps_cap"] = np.float64(cps_cap)
    if has_bg:
        from repro.kernels.traffic.ops import (_poisson_thresholds,
                                               _tail_bound)
        from repro.kernels.traffic.ref import geometric_tables
        from repro.net.engine import PACKET_BITS

        lam_w = np.asarray(lams, np.float64) * WINDOW
        n_draws = _tail_bound(float(lam_w.max()))
        dyn["keys"] = np.asarray(keys, np.uint32)
        dyn["thr"] = _poisson_thresholds(lam_w, n_draws)
        dyn["burst_thr"] = geometric_tables(1.0 / cfg.bg_burst_packets)[1]
        dyn["packet_bits"] = np.float32(PACKET_BITS)
    S = 1
    if mode == "bs":
        ts, te, sonu, srate, svalid = slot_arrays
        S = ts.shape[1]
        dyn.update(ts=np.asarray(ts, np.float64),
                   te=np.asarray(te, np.float64),
                   sonu=np.asarray(sonu, np.int64),
                   srate=np.asarray(srate, np.float64),
                   svalid=np.asarray(svalid, bool))

    max_slots = int(np.asarray(lay.seg_len).max())
    spec = (mode, R, U, N, S, int(n_pons), has_bg, has_cps,
            has_deadline, has_outage, bool(fill_unfinished),
            bool(use_pallas), max_slots, n_draws,
            len(dyn.get("burst_thr", ())),
            hash(np.asarray(lay.onu).tobytes()))

    return spec, dyn, n_draws


def phase_program(cfg, lay, rem_init, ready_t, mode: str, **kw):
    """The jitted (not yet compiled) program of one phase and its host
    inputs ``dyn``; arguments as for :func:`run_phase_device`.  Lower it
    under ``jax.enable_x64(True)`` — its queue state is float64."""
    spec, dyn, n_draws = _phase_inputs(cfg, lay, rem_init, ready_t, mode,
                                       **kw)
    return _build_program(spec, lay, n_draws), dyn


def run_phase_device(cfg, lay, rem_init, ready_t, mode: str, **kw,
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Run one phase on device.  Mirrors ``engine._run_phase``'s
    signature with the host ``_Stream`` replaced by its raw
    ``(keys, lams)`` so sampling fuses into the program.

    Returns ``(done_t, rem)`` numpy arrays, or ``None`` when the bg
    ring walk lost exactness (sustained overload aged a marginal head
    out of the ``HISTORY_CYCLES`` ring) — the caller re-runs the phase
    on the numpy engine and counts it (:func:`host_fallbacks`).
    """
    spec, dyn, n_draws = _phase_inputs(cfg, lay, rem_init, ready_t, mode,
                                       **kw)
    with jax.enable_x64(True):
        dyn_dev = {key: jnp.asarray(val) for key, val in dyn.items()}
        prog = _programs.get(spec)
        if prog is None:
            if len(_programs) > 64:
                _programs.clear()
            prog = _programs[spec] = _build_program(spec, lay, n_draws)
        done_t, rem, exact = prog(dyn_dev)
        if not bool(exact):
            return None
        return np.asarray(done_t), np.asarray(rem)
