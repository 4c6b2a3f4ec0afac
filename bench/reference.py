"""Plain reference of one synchronisation round on a (stacked) PON.

The benchmark's yardstick for ``correct``: the same round semantics as
the co-simulator under test, written out again from its published
description and imported from nothing in ``src/``.  A round is

* downstream: under ``fcfs`` every client's model copy queues at its
  ONU at t = 0 as best-effort traffic behind the assured background;
  under ``bs`` one reserved broadcast takes ``model_bits / (rate * eff)``
  plus propagation;
* upstream: each client's update enters its ONU's queue once its
  download and local training are done (``t_ready <= t + cycle``, at
  ``max(t_ready, t)``); ``fcfs`` serves assured background first, oldest
  head-of-line first, then FL queues by head-of-line age from the
  residual; ``bs`` serves each client only inside its slot of the slice
  (Algorithm 1 of arXiv:1911.07615, deadline sizing), and background
  never feeds back into FL service, so it is not simulated there;
* several PONs behind one CPS uplink split its per-cycle capacity
  max-min fair across their cycle demands, and a PON cut below its
  demand re-grants under its share.

Queues are fluid FIFOs of segments; a segment left with at most one
bit is dropped, and a client is done once at most one bit of its
update is left, at ``t + cycle + propagation``.

Background is the counter-based Poisson-burst stream of the program's
traffic model (a pure function of ``(seed, phase, round, pon, onu,
cycle)``; copied in :mod:`bench.streams`).  ``dtype`` sets the
precision of every time and bit quantity: float64 is the configuration
as stated, float32 is the lower-precision control.

Work is vectorised over (case, PON, ONU) with one host loop over
polling cycles.
"""
from __future__ import annotations

import numpy as np

from bench import streams

LIGHT_SPEED_FIBER = 2.0e8          # m/s
SEG_EPS = 1.0                      # bits: a segment this small is dropped
DONE_EPS = 1.0                     # bits: a client this close is done
CAP_EPS = 1e-9                     # bits: capacity exhausted
CHUNK = 1024                       # cycles of background sampled at once


def background_per_onu(clients, model_bits, load, line_rate, n_onus,
                       n_pons, t_round_hint):
    """Per-ONU background rate of each PON: the PON's offered load minus
    the training traffic of the clients placed on it, spread evenly."""
    total = n_onus * n_pons
    out = np.zeros(n_pons)
    for p in range(n_pons):
        m_ud = [c[2] for c in clients if (c[0] % total) // n_onus == p]
        train = 0.0
        if m_ud:
            train = (len(m_ud) * (model_bits + float(np.mean(m_ud)))
                     / max(t_round_hint, 1e-9))
        out[p] = max(load * line_rate - train, 0.0) / n_onus
    return out


def cps_waterfill(want, cap):
    """Max-min fair split of ``cap`` over the rows of ``want`` (G, P)."""
    want = np.asarray(want)
    eff = want.copy()
    over = want.sum(axis=1) > cap + CAP_EPS
    if not over.any():
        return eff
    w = want[over]
    P = w.shape[1]
    ws = np.sort(w, axis=1)
    prev = np.cumsum(ws, axis=1) - ws
    mu_k = (cap - prev) / (P - np.arange(P, dtype=w.dtype))
    k = np.argmax(mu_k <= ws, axis=1)
    mu = mu_k[np.arange(len(w)), k]
    eff[over] = np.minimum(w, mu[:, None])
    return eff


def slice_slots(profiles, capacity):
    """Slots of the BS slice for ``profiles`` = [(cid, t_ud, t_dl, m_ud,
    dist)]: ``(t_start, t_end, cid)`` lists in start order and the slice
    bandwidth.  Algorithm 1 with the deadline bandwidth (the smallest B
    at which earliest-ready-first slots all end by T^max)."""
    order = sorted(profiles, key=lambda c: c[1] + c[2])
    straggler = max(profiles, key=lambda c: c[1] + c[2])
    nabla = straggler[3] / capacity + straggler[4] / LIGHT_SPEED_FIBER
    t_max = straggler[1] + straggler[2] + nabla
    t_min = order[0][1] + order[0][2]
    tau = max(t_max - t_min, 1e-9)
    total_bits = sum(c[3] for c in profiles)
    need = suffix = 0.0
    for c in reversed(order):
        suffix += c[3]
        left = t_max - (c[1] + c[2])
        if left <= 0:
            need = float("inf")
            break
        need = max(need, suffix / left)
    demanded = max(total_bits / tau, need)
    bandwidth = min(demanded, capacity)
    cursor = t_min
    slots = []
    for c in order:
        start = max(cursor, c[1] + c[2])
        end = start + c[3] / bandwidth
        slots.append((start, end, c[0]))
        cursor = end
    slots.sort(key=lambda s: s[0])
    return slots, bandwidth


class _Background:
    """Per-ONU background FIFOs of one phase, over ``M`` = groups x PONs x
    ONUs queues, as cumulative packet counts per cycle."""

    def __init__(self, keys, lams, n_onus, burst_packets, dtype):
        self.keys, self.lams = keys, lams         # (G*P, 2), (G*P,)
        self.n_onus = n_onus
        self.burst_packets = burst_packets
        self.M = len(lams) * n_onus
        self.dtype = dtype
        self.cum = np.zeros((self.M, 0), np.int64)
        self.served = np.zeros(self.M, dtype)     # bits, from cycle 0
        self.pkt = dtype(streams.PACKET_BITS)
        self.rows = np.arange(self.M)
        self.off = self.rows.astype(np.int64) << 40

    def ensure(self, k):
        K = self.cum.shape[1]
        if k < K:
            return
        add = np.stack([
            streams.arrival_packets(key, lam, K, CHUNK, self.n_onus,
                                    self.burst_packets)
            for key, lam in zip(self.keys, self.lams)])   # (G*P, C, N)
        add = add.transpose(0, 2, 1).reshape(self.M, CHUNK)
        base = self.cum[:, -1:] if K else np.zeros((self.M, 1), np.int64)
        self.cum = np.concatenate([self.cum, base + np.cumsum(add, 1)], 1)
        self.K = self.cum.shape[1]
        # row offsets (cumulative counts stay far below 2**40) let one
        # searchsorted find every queue's head
        self.flat = (self.cum + self.off[:, None]).ravel()
        self.h = self._head()

    def backlog(self, k):
        return self.cum[:, k].astype(self.dtype) * self.pkt - self.served

    def _head(self):
        """Cycle of each queue's head segment: the first whose cumulative
        size exceeds what was served by more than ``SEG_EPS``."""
        q = np.floor((self.served + SEG_EPS) / self.pkt).astype(np.int64)
        return np.searchsorted(self.flat, q + 1 + self.off) - self.rows * (
            self.K)

    def serve(self, grant):
        """Drain ``grant`` bits from each FIFO's head; a segment left with
        at most ``SEG_EPS`` bits is dropped whole."""
        self.served = self.served + grant
        # a head only moves forward; look again where it no longer holds
        q = np.floor((self.served + SEG_EPS) / self.pkt).astype(np.int64)
        moved = np.flatnonzero(
            self.cum[self.rows, np.minimum(self.h, self.K - 1)] <= q)
        if len(moved):
            self.h[moved] = np.searchsorted(
                self.flat, q[moved] + 1 + self.off[moved]) - moved * self.K
        boundary = self.cum[self.rows, np.maximum(self.h - 1, 0)].astype(
            self.dtype) * self.pkt
        self.served = np.where(self.h > 0,
                               np.maximum(self.served, boundary),
                               self.served)


def _fifo_take(backlog, key, cap):
    """FCFS grants over rows of ``backlog`` (R, N): queues with backlog in
    ascending ``key`` (ties by index) each take ``min(backlog, cap
    left)``, the capacity counted down in that order, until it is spent.
    Returns (grants, capacity left)."""
    R, N = backlog.shape
    order = np.argsort(np.where(backlog > 0, key, np.inf), axis=1,
                       kind="stable")
    rows = np.arange(R)[:, None]
    b = backlog[rows, order]
    caps = np.empty((R, N + 1), backlog.dtype)
    caps[:, 0] = cap
    caps[:, 1:] = -b
    np.add.accumulate(caps, axis=1, out=caps)
    before = caps[:, :-1]
    spent = before <= CAP_EPS
    take = np.where(spent, 0.0, np.minimum(b, np.maximum(before, 0.0)))
    stop = ((before - take) <= CAP_EPS) & (take > 0)
    grant = np.empty_like(backlog)
    grant[rows, order] = take
    left = np.where(stop.any(axis=1), 0.0, caps[:, -1])
    return grant, left


class _Phase:
    """One transfer phase of ``G`` groups (a case, or a round of a
    timeline) over ``P`` PONs of ``N`` ONUs; clients are columns."""

    def __init__(self, pon, P, onu_of, bits0, ready, dtype):
        self.N = pon["n_onus"]
        self.P = P
        self.dtype = dtype
        self.cyc = dtype(pon["cycle_time_s"])
        self.prop = dtype(pon["distance_m"] / LIGHT_SPEED_FIBER)
        self.G, self.U = bits0.shape
        self.onu = onu_of                         # (G, U) flat p*N + o
        self.rem = bits0.astype(dtype)
        self.ready = ready.astype(dtype)
        self.waiting = bits0 > 0
        self.queued = np.zeros_like(self.waiting)
        self.push_t = np.full(bits0.shape, np.inf, dtype)
        self.push_seq = np.zeros(bits0.shape, np.int64)
        self.done_t = np.full(bits0.shape, np.nan, dtype)
        self.cap = dtype(pon["line_rate_bps"]) * self.cyc * dtype(
            pon["efficiency"])
        self.rows = np.arange(self.G)[:, None]

    def push(self, k, t):
        new = self.waiting & (self.ready <= t + self.cyc)
        if new.any():
            self.push_t = np.where(new, np.maximum(self.ready, t),
                                   self.push_t)
            self.push_seq = np.where(
                new, k * self.U + np.arange(self.U), self.push_seq)
            self.waiting &= ~new
            self.queued |= new

    def fl_state(self):
        """Per-ONU FL backlog and head-of-line push time, (G, P*N)."""
        live = self.queued & (self.done_t != self.done_t)
        PN = self.P * self.N
        g, u = np.nonzero(live)
        at = g * PN + self.onu[g, u]
        backlog = np.bincount(at, self.rem[g, u], self.G * PN).astype(
            self.dtype).reshape(self.G, PN)
        hol = np.full(self.G * PN, np.inf, self.dtype)
        np.minimum.at(hol, at, self.push_t[g, u])
        return backlog, hol.reshape(self.G, PN), live

    def serve_fl(self, grant, t, live):
        """Drain each ONU's FL FIFO (push order) by its grant."""
        budget = grant.copy()
        g, u = np.nonzero(live & (grant[self.rows, self.onu] > 0))
        if not len(g):
            return
        order = np.lexsort((self.push_seq[g, u], self.onu[g, u], g))
        g, u = g[order], u[order]
        while len(g):
            # the first client of each (group, ONU) still in the list
            key = g * (self.P * self.N) + self.onu[g, u]
            first = np.r_[True, key[1:] != key[:-1]]
            gf, uf = g[first], u[first]
            of = self.onu[gf, uf]
            b = budget[gf, of]
            go = b > CAP_EPS
            take = np.where(go, np.minimum(self.rem[gf, uf], b), 0.0)
            left = self.rem[gf, uf] - take
            b = b - take
            drained = go & (left <= SEG_EPS)
            b = np.where(drained, np.maximum(b - left, 0.0), b)
            budget[gf, of] = b
            self.rem[gf, uf] = left
            fin = go & (left <= DONE_EPS)
            self.done_t[gf[fin], uf[fin]] = t + self.cyc + self.prop
            g, u = g[~first], u[~first]

    def finish(self, t):
        left = self.done_t != self.done_t
        self.done_t = np.where(left & (self.rem > 0), t + self.prop,
                               self.done_t)
        return self.done_t


def _clock(cyc, n, dtype):
    """The cycle-start clock ``t_k``: ``t += cycle`` repeated, in order."""
    steps = np.full(n, cyc, dtype)
    steps[0] = 0
    return np.add.accumulate(steps, dtype=dtype)


def _fcfs_phase(ph, bg, cps_cap, max_t):
    P, N, G = ph.P, ph.N, ph.G
    t_seq = _clock(ph.cyc, 1 << 20, ph.dtype)
    cap = np.full(G * P, ph.cap, ph.dtype)
    k = 0
    while True:
        t = t_seq[k]
        active = (ph.done_t != ph.done_t) & (ph.rem > 0)
        if not active.any() or t >= max_t:
            break
        ph.push(k, t)
        bg.ensure(k)
        b_bg = bg.backlog(k).reshape(G * P, N)
        hol_bg = np.where(b_bg > 0, t_seq[np.minimum(bg.h, k)].reshape(
            G * P, N), np.inf)
        b_fl, hol_fl, live = ph.fl_state()
        b_fl = b_fl.reshape(G * P, N)
        hol_fl = hol_fl.reshape(G * P, N)
        any_fl = live.any()

        def grant(c):
            g_bg, left = _fifo_take(b_bg, hol_bg, c)
            if not any_fl:
                return g_bg, np.zeros_like(b_fl)
            g_fl, _ = _fifo_take(b_fl, hol_fl,
                                 np.where(left > CAP_EPS, left, 0.0))
            return g_bg, g_fl

        g_bg, g_fl = grant(cap)
        if cps_cap is not None:
            want = (g_bg.sum(-1) + g_fl.sum(-1)).reshape(G, P)
            eff = cps_waterfill(want, ph.dtype(cps_cap)).reshape(-1)
            cut = eff < want.reshape(-1)
            if cut.any():
                r_bg, r_fl = grant(np.where(cut, np.minimum(cap, eff), cap))
                g_bg = np.where(cut[:, None], r_bg, g_bg)
                g_fl = np.where(cut[:, None], r_fl, g_fl)
        bg.serve(g_bg.reshape(-1))
        if any_fl:
            ph.serve_fl(g_fl.reshape(G, P * N), t, live)
        k += 1
    return ph.finish(t_seq[k])


def _bs_phase(ph, slots, cps_cap, max_t):
    """``slots[g][p]`` = (t_start, t_end, flat ONU) arrays in start order
    and the slice bandwidth; a slot is open in the cycles it overlaps,
    with one cycle of grace at its end."""
    P, N, G = ph.P, ph.N, ph.G
    t_seq = _clock(ph.cyc, 1 << 20, ph.dtype)
    cyc = ph.cyc
    k = 0
    while True:
        t = t_seq[k]
        active = (ph.done_t != ph.done_t) & (ph.rem > 0)
        if not active.any() or t >= max_t:
            break
        ph.push(k, t)
        b_fl, _, live = ph.fl_state()

        def grant(cap):
            out = np.zeros((G, P * N), ph.dtype)
            for g in range(G):
                for p in range(P):
                    if slots[g][p] is None:
                        continue
                    ts, te, onu, rate = slots[g][p]
                    # slots are back to back, so the open ones are a run
                    hi = np.searchsorted(ts, t + cyc, side="left")
                    lo = np.searchsorted(te + cyc, t, side="right")
                    spent = ph.dtype(0)
                    for s in range(lo, hi):
                        over = (min(te[s] + cyc, t + cyc) - max(ts[s], t))
                        bits = min(rate * max(over, ph.dtype(0)),
                                   b_fl[g, onu[s]], cap[g, p] - spent)
                        if bits > 0:
                            out[g, onu[s]] += bits
                            spent += bits
            return out

        cap = np.full((G, P), ph.cap, ph.dtype)
        g_fl = grant(cap)
        if cps_cap is not None:
            want = g_fl.reshape(G, P, N).sum(-1)
            eff = cps_waterfill(want, ph.dtype(cps_cap))
            cut = eff < want
            if cut.any():
                again = grant(np.where(cut, np.minimum(cap, eff), cap))
                g_fl = np.where(np.repeat(cut, N, axis=1), again, g_fl)
        ph.serve_fl(g_fl, t, live)
        k += 1
    return ph.finish(t_seq[k])


def simulate_rounds(pon, topo, rounds, t_round_hint=10.0, max_t=600.0,
                    dtype=np.float64):
    """Reference rounds, one per entry of ``rounds``: dicts with
    ``clients`` [(cid, t_ud, m_ud_bits, distance_m)], ``model_bits``,
    ``t_aggregate``, ``load``, ``policy``, ``seed`` and ``stream_round``.
    Every round has as many clients as the first.  Returns per round a
    dict of ``dl_done``, ``ready`` and ``ul_done`` (client id -> s) and
    ``sync_time``."""
    dtype = np.dtype(dtype).type
    N = pon["n_onus"]
    P = topo.get("n_pons", 1)
    cps = topo.get("cps_rate_bps")
    cps_cap = None if cps is None else float(cps) * pon["cycle_time_s"]
    total = N * P
    line = pon["line_rate_bps"]
    prop = dtype(pon["distance_m"] / LIGHT_SPEED_FIBER)
    out = [None] * len(rounds)
    for policy in ("fcfs", "bs"):
        idx = [i for i, r in enumerate(rounds) if r["policy"] == policy]
        if not idx:
            continue
        sel = [rounds[i] for i in idx]
        cid = np.array([[c[0] for c in r["clients"]] for r in sel])
        t_ud = np.array([[c[1] for c in r["clients"]] for r in sel])
        m_ud = np.array([[c[2] for c in r["clients"]] for r in sel])
        onu = cid % total                                 # p*N + o
        G = len(sel)
        mb = np.array([r["model_bits"] for r in sel])[:, None]
        if policy == "fcfs":
            rates = [background_per_onu(r["clients"], r["model_bits"],
                                        r["load"], line, N, P,
                                        t_round_hint) for r in sel]

            def phase_bg(phase):
                keys = [streams.stream_key(r["seed"], phase,
                                           r["stream_round"], p)
                        for r in sel for p in range(P)]
                lams = [streams.burst_lambda(rates[g][p],
                                             pon["cycle_time_s"],
                                             pon["bg_burst_packets"])
                        for g in range(G) for p in range(P)]
                return _Background(keys, lams, N, pon["bg_burst_packets"],
                                   dtype)

            dl = _Phase(pon, P, onu, np.broadcast_to(mb, cid.shape),
                        np.zeros(cid.shape), dtype)
            dl_done = _fcfs_phase(dl, phase_bg(0), cps_cap, max_t)
        else:
            rate_eff = dtype(line) * dtype(pon["efficiency"])
            dl_done = np.broadcast_to(
                (mb.astype(dtype) / rate_eff + prop), cid.shape)
        ready = dl_done + t_ud.astype(dtype)
        ul = _Phase(pon, P, onu, m_ud, ready, dtype)
        if policy == "fcfs":
            ul_done = _fcfs_phase(ul, phase_bg(1), cps_cap, max_t)
        else:
            slots = []
            for g, r in enumerate(sel):
                row = []
                for p in range(P):
                    profs = [(c[0], c[1], float(dl_done[g, j]), c[2], c[3])
                             for j, c in enumerate(r["clients"])
                             if (c[0] % total) // N == p]
                    if not profs:
                        row.append(None)
                        continue
                    sl, bw = slice_slots(profs, float(line)
                                         * pon["efficiency"])
                    row.append((np.array([s[0] for s in sl], dtype),
                                np.array([s[1] for s in sl], dtype),
                                np.array([s[2] % total for s in sl]),
                                dtype(bw)))
                slots.append(row)
            ul_done = _bs_phase(ul, slots, cps_cap, max_t)
        for g, i in enumerate(idx):
            ids = [int(c) for c in cid[g]]
            r = rounds[i]
            out[i] = {
                "dl_done": dict(zip(ids, map(float, dl_done[g]))),
                "ready": dict(zip(ids, map(float, ready[g]))),
                "ul_done": dict(zip(ids, map(float, ul_done[g]))),
                "sync_time": float(ul_done[g].max()) + r["t_aggregate"],
            }
    return out
