"""Vectorized batched PON round engine.

``repro.net.sim`` advances one polling cycle at a time through Python
dicts and per-cycle ``sorted()`` calls over ``OnuQueue`` segment lists; a
128-ONU round costs thousands of interpreted cycles and a full Fig. 2b
sweep takes minutes.  This module keeps that simulator as the semantic
reference and re-expresses one cycle as a handful of array operations
over *all* ONUs at once, with a batch axis over sweep cases
(seed x load x policy) — and, under a ``MultiPonTopology``, over the
cases' wavelength segments too: rows become flattened ``(case, pon)``
pairs over per-PON ONU columns, coupled each cycle by the CPS
waterfill (``repro.net.multi_pon``):

* queue backlogs are ``(n_cases, n_onus)`` float arrays; FL queues are
  tracked per client in a static ``(onu, client_id)``-sorted layout so
  per-ONU aggregates are ``np.add.reduceat`` calls;
* the FCFS DBA's "assured background oldest-first, then best-effort FL
  oldest-first" becomes a stable argsort by head-of-line age plus
  prefix-sum waterfilling of the cycle capacity;
* the Sliced DBA's slot grants are an overlap computation over the slot
  arrays (``repro.core.scheduler.slots_to_arrays``) plus the same
  prefix-sum capacity cap;
* background FIFO state (head-of-line ages, the reference's 1-bit
  segment compaction) is kept exactly via per-ONU head pointers into the
  arrival history, so the engine reproduces the reference's per-client
  ``dl_done``/``ready``/``ul_done`` within float tolerance when both
  consume the same arrival process (property-tested).

Public API: ``SweepCase`` + ``simulate_round_sweep`` (a whole sweep as
one stacked simulation — legacy kwarg form; prefer building a
``repro.net.SweepSpec`` and calling ``simulate(spec)``);
``repro.net.sim.simulate_round`` uses this as its default backend.
Multi-tenant cases (``SweepCase.jobs``) add a job axis: columns gain a
job binding next to ``cid_of`` and each cycle's FL capacity is split
across jobs by the case's fairness policy (``repro.net.jobs``).
"""
from __future__ import annotations

import functools
import warnings
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.scheduler import schedule_slots, slots_to_arrays
from repro.core.slicing import ClientProfile, SliceSpec, compute_slice
from repro.net.jobs import (
    FAIRNESS_POLICIES,
    compute_job_stats,
    job_fair_split,
    validate_case_jobs,
)
from repro.net.multi_pon import (
    MultiPonTopology,
    cps_waterfill,
    pon_bg_rates,
)
from repro.net.traffic import (
    PACKET_BITS,
    background_rate_for_load,
    burst_lambda,
)

CAP_EPS = 1e-9       # the DBAs' "capacity exhausted" threshold
SEG_EPS = 1.0        # OnuQueue.serve: segments under 1 bit are compacted
EPS_BITS = 1.0       # sim._settle: a client is done below 1 remaining bit
_IKEY_INF = np.iinfo(np.int64).max // 4


@dataclass(frozen=True)
class SweepCase:
    """One cell of a sweep: a workload under (policy, load, seed).

    ``dl_arrivals``/``ul_arrivals`` optionally inject a precomputed
    per-cycle background arrival matrix ``(n_cycles, n_onus)`` (bits) for
    each phase — the parity-test hook; cycles beyond the matrix see zero
    arrivals (columns are global ONUs — ``n_pons * n_onus`` wide under a
    topology).  When absent, arrivals come from the case's counter-based
    Poisson-burst stream keyed by ``(seed, phase, stream_round, pon)``
    (``repro.kernels.traffic``) — identical regardless of chunking and
    O(1)-seekable, so a multi-round timeline can address round
    ``stream_round``'s arrivals directly.

    ``no_dl_ids`` lists clients that skip the model download (their
    ``dl_done`` is 0.0): the multi-round timeline's deadline carriers,
    which resume a partial upload instead of fetching a fresh model.

    ``topology`` stacks the case over several wavelength/OLT segments
    sharing a CPS uplink (``repro.net.multi_pon.MultiPonTopology``);
    every case of a sweep must share one topology. ``None`` is the
    single-PON network described by the ``PONConfig`` alone.

    ``jobs`` (tuple of ``repro.net.jobs.JobSpec``) makes the case
    multi-tenant: the jobs must partition ``workload.clients`` exactly,
    each job's downlink broadcasts its OWN ``model_bits``, and every
    cycle's FL capacity is split across jobs by ``fairness``
    (``"maxmin"`` | ``"weighted"`` | ``"deadline"``) before the
    per-queue grants. A sweep where every case has exactly one job runs
    the single-tenant path bitwise and only adds per-job stats.
    """

    workload: "FLRoundWorkload"  # noqa: F821  (imported lazily, no cycle)
    load: float
    policy: str                  # "fcfs" | "bs"
    seed: int = 0
    dl_arrivals: Optional[np.ndarray] = None
    ul_arrivals: Optional[np.ndarray] = None
    stream_round: int = 0
    no_dl_ids: frozenset = frozenset()
    topology: Optional[MultiPonTopology] = None
    jobs: Optional[tuple] = None          # Tuple[JobSpec, ...]
    fairness: str = "maxmin"


# ---------------------------------------------------------------------------
# client layout: (local_onu, slot) columns, per-PON client bindings
# ---------------------------------------------------------------------------


class _Layout:
    """Static slot layout shared by every row of a sweep.

    Rows are flattened ``(case, pon)`` pairs (case-major); columns are
    ``(local_onu, slot)`` pairs, ascending, where ONU ``o`` carries
    ``max_p |clients on (p, o)|`` slots — so per-ONU reductions are
    contiguous ``reduceat`` segments shared by every row, while each
    row binds its own PON's clients to the slots (``cid_of[p, col]``;
    a column is dead — ``part`` False — in rows whose PON or case
    doesn't bind it).  Slots within an ONU are bound in ascending
    ``client_id`` order, so the settle order (ascending id within an
    ONU) is the column order, exactly the PR 2 single-PON layout when
    ``n_pons == 1``.  Column count is the *per-PON maximum*, not the
    client union — a 32-PON stack of 4 096 clients keeps ~128 columns
    per row instead of 4 096, which is what makes stacking win over a
    per-PON loop.

    Client placement: global onu = id % (n_pons * n_onus); PON =
    onu // n_onus, local onu = onu % n_onus.
    """

    def __init__(self, cases: Sequence[SweepCase], n_onus: int,
                 n_pons: int = 1):
        total = n_onus * n_pons
        ids = sorted(
            {c.client_id for case in cases for c in case.workload.clients}
        )
        if not ids:
            raise ValueError("sweep needs at least one client")
        buckets: Dict[tuple, List[int]] = {}
        for i in ids:                       # ascending id within buckets
            o = i % total
            buckets.setdefault((o // n_onus, o % n_onus), []).append(i)
        slots = np.zeros(n_onus, np.int64)
        for (_, o), lst in buckets.items():
            slots[o] = max(slots[o], len(lst))
        self.onu = np.repeat(np.arange(n_onus, dtype=np.int64), slots)
        slot_off = np.zeros(n_onus + 1, np.int64)
        np.cumsum(slots, out=slot_off[1:])
        nU = self.n_clients = int(slot_off[-1])
        self.pos = np.arange(nU, dtype=np.int64)
        # per-PON slot binding: which client id a column carries
        self.cid_of = np.full((n_pons, nU), -1, np.int64)
        colmap: Dict[int, int] = {}
        for (p, o), lst in buckets.items():
            for s, cid in enumerate(lst):
                col = int(slot_off[o]) + s
                self.cid_of[p, col] = cid
                colmap[cid] = col
        starts = [0] + [
            j for j in range(1, nU) if self.onu[j] != self.onu[j - 1]
        ]
        self.seg_starts = np.asarray(starts, np.int64)
        self.seg_onus = self.onu[self.seg_starts]
        self.seg_len = np.diff(np.append(self.seg_starts, nU))
        self.single = bool(self.seg_len.max() == 1)
        # one slot per ONU in ONU order: per-ONU aggregates are the
        # column arrays themselves (no scatter, no allocation)
        self.identity = self.single and nU == n_onus and bool(
            (self.onu == np.arange(n_onus)).all()
        )

        B = len(cases)
        R = B * n_pons
        self.n_pons = n_pons
        self.part = np.zeros((R, nU), bool)
        self.t_ud = np.zeros((R, nU))
        self.m_ud = np.zeros((R, nU))
        self.dist = np.full((R, nU), 20_000.0)
        self.list_pos = np.zeros((R, nU), np.int64)
        for b, case in enumerate(cases):
            seen = set()
            for p, c in enumerate(case.workload.clients):
                if c.client_id in seen:
                    raise ValueError(
                        f"duplicate client_id {c.client_id} in case {b}"
                    )
                seen.add(c.client_id)
                o = c.client_id % total
                r = b * n_pons + o // n_onus
                j = colmap[c.client_id]
                self.part[r, j] = True
                self.t_ud[r, j] = c.t_ud
                self.m_ud[r, j] = c.m_ud_bits
                self.dist[r, j] = c.distance_m
                self.list_pos[r, j] = p

    def rows(self, sel: np.ndarray) -> "_Layout":
        """Row-sliced view for a sub-batch of rows (columns shared)."""
        sub = object.__new__(_Layout)
        sub.__dict__.update(self.__dict__)
        for name in ("part", "t_ud", "m_ud", "dist", "list_pos"):
            setattr(sub, name, getattr(self, name)[sel])
        return sub


_LAYOUT_CACHE: "OrderedDict[tuple, _Layout]" = OrderedDict()
_LAYOUT_CACHE_MAX = 16


def _layout_for(cases: Sequence[SweepCase], n_onus: int,
                n_pons: int = 1) -> _Layout:
    """Memoized ``_Layout`` construction.

    The layout depends only on the client tuples (ids, t_ud, m_ud,
    distance — ``ClientProfile`` is frozen/hashable) and the topology
    shape, and is never mutated after ``__init__`` — every phase of
    every round of a timeline with stable membership rebuilds the exact
    same python bucket/colmap loops.  A small LRU keyed by the client
    tuples removes that rebuild; elastic-membership timelines simply
    rotate through the LRU.
    """
    try:
        key = (int(n_onus), int(n_pons),
               tuple(tuple(case.workload.clients) for case in cases))
    except TypeError:             # unhashable client type: build fresh
        return _Layout(cases, n_onus, n_pons)
    lay = _LAYOUT_CACHE.get(key)
    if lay is None:
        lay = _Layout(cases, n_onus, n_pons)
        _LAYOUT_CACHE[key] = lay
        while len(_LAYOUT_CACHE) > _LAYOUT_CACHE_MAX:
            _LAYOUT_CACHE.popitem(last=False)
    else:
        _LAYOUT_CACHE.move_to_end(key)
    return lay


# ---------------------------------------------------------------------------
# arrival streams
# ---------------------------------------------------------------------------

_CHUNK = 1024
_CHUNK_TARGET_CELLS = 1 << 22     # bound per-chunk sampler memory


class _CaseFixed:
    """Replays an injected ``(n_cycles, n_onus)`` arrival matrix."""

    def __init__(self, rows: np.ndarray, n_onus: int):
        rows = np.asarray(rows, np.float64)
        if rows.ndim != 2 or rows.shape[1] != n_onus:
            raise ValueError(f"arrivals must be (n_cycles, {n_onus})")
        self.rows = rows
        self.n = n_onus

    def chunk(self, cycle0: int, length: int) -> np.ndarray:
        out = np.zeros((length, self.n))
        avail = self.rows[cycle0:cycle0 + length]
        out[: len(avail)] = avail
        return out


class _Stream:
    """Batched counter-based arrival rows, chunked and O(1)-seekable.

    Sampled cases (``(key, lam)`` pairs) are drawn in ONE vectorized
    sampler call per chunk; injected cases replay their fixed matrices.
    Chunk boundaries never affect values (counter-based sampler), so the
    adaptive chunk length is purely a memory/speed knob.
    """

    def __init__(self, entries: List, n_onus: int, inv_burst: float,
                 packet_bits: float = PACKET_BITS):
        self.n = n_onus
        self.inv_burst = inv_burst
        self.packet_bits = packet_bits
        self.fixed = [(i, e) for i, e in enumerate(entries)
                      if isinstance(e, _CaseFixed)]
        self.sampled = [(i, e) for i, e in enumerate(entries)
                        if not isinstance(e, _CaseFixed)]
        self.B = len(entries)
        if self.sampled:
            self.keys = np.stack([np.asarray(e[0], np.uint32)
                                  for _, e in self.sampled])
            self.lams = np.array([e[1] for _, e in self.sampled],
                                 np.float32)
            self.rows_sel = np.array([i for i, _ in self.sampled])
        self.chunk_len = int(np.clip(
            _CHUNK_TARGET_CELLS // max(self.B * n_onus, 1), 64, _CHUNK
        ))
        self._buf: Optional[np.ndarray] = None
        self._base = 0

    def row(self, k: int) -> np.ndarray:
        if self._buf is None or k >= self._base + self._buf.shape[1]:
            from repro.kernels.traffic.ops import sample_arrival_bits

            self._base = k
            buf = np.zeros((self.B, self.chunk_len, self.n))
            if self.sampled and float(self.lams.max()) > 0.0:
                buf[self.rows_sel] = sample_arrival_bits(
                    self.keys, k, self.chunk_len, self.n, self.lams,
                    self.inv_burst, self.packet_bits,
                )
            for i, e in self.fixed:
                buf[i] = e.chunk(k, self.chunk_len)
            self._buf = buf
        return self._buf[:, k - self._base, :]


# ---------------------------------------------------------------------------
# background queues: exact FIFO semantics over the arrival history
# ---------------------------------------------------------------------------


class _BgQueues:
    """Batched per-ONU background FIFOs on a chunked prefix-sum history.

    One segment per (cycle, ONU) arrival, stored as the *cumulative*
    arrival bits per queue (``prefix[b, j, n]`` = bits pushed through
    cycle ``j``). A queue's state is then just its total drained offset
    ``D``: backlog is ``cum - D``, the head-of-line segment is the first
    cycle whose prefix exceeds ``D``, and ``OnuQueue.serve``'s
    sequential drain collapses to one closed-form advance —
    ``D' = D + grant``, plus the reference's ≤1-bit compaction charge,
    which can only trigger at the final partial segment (a genuine
    sub-bit residue requires the budget to die inside that segment), so
    a single snap reproduces the walk exactly.
    """

    def __init__(self, B: int, n_onus: int):
        self.B, self.N = B, n_onus
        self.ptr = np.zeros((B, n_onus), np.int64)   # head segment cycle
        self.drained = np.zeros((B, n_onus))         # incl. snap charges
        self.cum = np.zeros((B, n_onus))             # pushed through k
        self.backlog = np.zeros((B, n_onus))
        self._chunks: Dict[int, np.ndarray] = {}

    def push(self, k: int, bits: np.ndarray):
        cidx, off = divmod(k, _CHUNK)
        buf = self._chunks.get(cidx)
        if buf is None:
            buf = self._chunks[cidx] = np.empty((self.B, _CHUNK, self.N))
        fresh = (self.backlog <= 0.0) & (bits > 0.0)
        np.add(self.cum, bits, out=self.cum)
        buf[:, off, :] = self.cum
        self.backlog = self.cum - self.drained
        # an arrival into an empty queue is the new head; every other
        # event keeps ptr exact (full drains set k+1, partial drains
        # advance it), so head-of-line lookups are pure gathers
        self.ptr = np.where(fresh, k, self.ptr)
        if k and off == 0:
            live = np.where(self.backlog > 0.0, self.ptr, k)
            floor = int(live.min()) // _CHUNK
            for c in [c for c in self._chunks if c < floor]:
                del self._chunks[c]

    def _prefix_at(self, rb, rn, idx) -> np.ndarray:
        """Prefix values at absolute cycle ``idx`` for a flat subset."""
        out = np.zeros(len(rb))
        for cidx, buf in self._chunks.items():
            base = cidx * _CHUNK
            m = (idx >= base) & (idx < base + _CHUNK)
            if m.any():
                out[m] = buf[rb[m], idx[m] - base, rn[m]]
        return out

    _ADV_W = 32                   # window width per advance hop

    def _advance(self, rb, rn, ptr, target, k: int) -> np.ndarray:
        """First cycle ≤ k whose prefix exceeds ``target`` (per queue).

        A drain can cross tens of segments (a near-capacity grant over
        packet-sized arrivals), so the walk gathers a prefix *window*
        per queue and jumps to the first exceeding cycle — one gather +
        argmax per hop instead of one gather per segment. Queues still
        unresolved after a few hops (pathological) fall back to a
        per-queue binary search over their own prefix row.
        """
        # single steps first: the marginal (partially-granted) queue
        # usually crosses 1-2 segments, so (P,) gathers win
        for _ in range(3):
            move = (ptr <= k) & (self._prefix_at(rb, rn, ptr) <= target)
            if not move.any():
                return ptr
            ptr = ptr + move
        # windowed hops for the long walks (a big grant over many
        # packet-sized segments): one gather + argmax per hop
        W = self._ADV_W
        offs = np.arange(W, dtype=np.int64)
        sel = np.nonzero(move)[0]
        sptr = ptr[sel]
        srb, srn, star = rb[sel], rn[sel], target[sel]
        for _ in range(3):
            idx = sptr[:, None] + offs
            valid = idx <= k
            slab = self._prefix_at(
                np.broadcast_to(srb[:, None], idx.shape).ravel(),
                np.broadcast_to(srn[:, None], idx.shape).ravel(),
                np.minimum(idx, k).ravel(),
            ).reshape(idx.shape)
            stop = (slab > star[:, None]) | ~valid
            first = np.argmax(stop, axis=1)
            found = stop[np.arange(len(sptr)), first]
            sptr = np.where(found, sptr + first, sptr + W)
            if found.all():
                ptr[sel] = sptr
                return ptr
        ptr[sel] = sptr
        rows = sel[np.nonzero(~found)[0]]
        for i in rows:
            b, n, t = int(rb[i]), int(rn[i]), target[i]
            j = int(ptr[i])
            while j <= k:
                cidx, off = divmod(j, _CHUNK)
                buf = self._chunks[cidx]
                row = buf[b, off:min(_CHUNK, k + 1 - cidx * _CHUNK), n]
                pos = int(np.searchsorted(row, t, side="right"))
                if pos < len(row):
                    j = cidx * _CHUNK + off + pos
                    break
                j = (cidx + 1) * _CHUNK
            ptr[i] = j
        return ptr

    def hol_key(self) -> np.ndarray:
        """FCFS sort key: the head segment's arrival cycle (cycle times
        are strictly increasing, so ordering by ``ptr`` is ordering by
        head-of-line age — integer argsort, no time lookup)."""
        return np.where(self.backlog > 0.0, self.ptr, _IKEY_INF)

    def serve(self, grants: np.ndarray, k: int):
        # fast path: a grant equal to the whole backlog (the common
        # under-capacity case) drains the queue exactly
        full = (grants > 0.0) & (grants == self.backlog)
        budget = np.where(full, 0.0, grants)
        if full.any():
            self.drained = np.where(full, self.cum, self.drained)
            self.backlog = np.where(full, 0.0, self.backlog)
            self.ptr = np.where(full, k + 1, self.ptr)
        part = budget > CAP_EPS
        if not part.any():
            return
        # partial grants: closed-form drain on the prefix history
        rb, rn = np.nonzero(part)
        target = self.drained[rb, rn] + budget[rb, rn]
        ptr = self._advance(rb, rn, self.ptr[rb, rn], target, k)
        seg_end = self._prefix_at(rb, rn, ptr)
        in_hist = ptr <= k
        snap = in_hist & (seg_end - target <= SEG_EPS)
        drained = np.where(snap, seg_end, target)
        bklg = np.where(in_hist, self.cum[rb, rn] - drained, 0.0)
        low = bklg < 0.5
        drained = np.where(low, self.cum[rb, rn], drained)
        bklg = np.where(low, 0.0, bklg)
        ptr = np.where(low, k + 1, ptr)
        # a snap consumed through the segment at ptr; the new head is
        # the next *arrival* cycle (prefix > drained), not blindly
        # ptr+1, which may be a zero-arrival cycle and would corrupt
        # the FCFS head-of-line age (the reference's restore loop)
        adv = np.nonzero(snap & ~low)[0]
        if len(adv):
            ptr[adv] = self._advance(
                rb[adv], rn[adv], ptr[adv] + 1, drained[adv], k
            )
        self.drained[rb, rn] = drained
        self.ptr[rb, rn] = ptr
        self.backlog[rb, rn] = bklg


# ---------------------------------------------------------------------------
# per-cycle kernels
# ---------------------------------------------------------------------------


def _waterfill(backlog: np.ndarray, hol_fn, cap: np.ndarray) -> np.ndarray:
    """Oldest-first sequential ``take = min(backlog, cap)`` grants,
    expressed as stable argsort + prefix-sum room.

    ``hol_fn`` returns any array that sorts queues by head-of-line age
    (float times, or integer arrival cycles — strictly-increasing cycle
    times make them order-equivalent). It is called lazily: when total
    demand sits at least one bit under capacity, every queue is granted
    its full backlog regardless of age order (room >= suffix >= own
    backlog for every prefix), so the sort — and computing head-of-line
    ages at all — is skipped.
    """
    hard = backlog.sum(axis=1) > cap - 1.0
    if not np.any(hard):
        return backlog.copy()
    grants = backlog.copy()
    hb = backlog[hard]
    hol = hol_fn()[hard]
    order = np.argsort(hol, axis=1, kind="stable")
    rows = np.arange(hb.shape[0])[:, None]
    b_s = hb[rows, order]
    prefix = np.cumsum(b_s, axis=1)
    room = cap[hard][:, None] - (prefix - b_s)
    g_s = np.where(room > CAP_EPS, np.minimum(b_s, room), 0.0)
    g = np.empty_like(g_s)
    g[rows, order] = g_s
    grants[hard] = g
    return grants


class _FLQueues:
    """Batched per-ONU FL FIFOs over the static client layout."""

    def __init__(self, lay: _Layout, B: int, n_onus: int):
        self.lay = lay
        self.B, self.N = B, n_onus
        nU = lay.n_clients
        self.qb = np.zeros((B, nU))
        self.push_key = np.full((B, nU), _IKEY_INF, np.int64)
        self.push_time = np.zeros((B, nU))
        self._bidx = np.arange(B)[:, None]
        # one client per ONU: FIFO heads are the clients themselves, so
        # drains and reductions collapse to direct column scatters
        self.single = lay.single

    def push(self, mask: np.ndarray, bits: np.ndarray, k: int, t: float,
             ready_t: np.ndarray):
        lay = self.lay
        self.qb = np.where(mask, bits, self.qb)
        key = k * np.int64(lay.n_clients + 1) + lay.list_pos
        self.push_key = np.where(mask, key, self.push_key)
        self.push_time = np.where(
            mask, np.maximum(ready_t, t), self.push_time
        )

    def backlog_per_onu(self, mask: Optional[np.ndarray] = None
                        ) -> np.ndarray:
        """Per-ONU FL backlog; ``mask`` (multi-tenant jobs) restricts
        the sum to one job's columns. ``mask=None`` keeps the
        single-tenant paths bitwise (including the aliased identity
        view)."""
        lay = self.lay
        if lay.identity:
            if mask is None:
                return self.qb      # aliased view: callers read only
            return np.where(mask, self.qb, 0.0)
        qb = self.qb if mask is None else np.where(mask, self.qb, 0.0)
        out = np.zeros((self.B, self.N))
        if self.single:
            out[:, lay.seg_onus] = qb
        else:
            out[:, lay.seg_onus] = np.add.reduceat(
                qb, lay.seg_starts, axis=1
            )
        return out

    def _heads(self, mask: Optional[np.ndarray] = None):
        """(head_exists, head_pos, budget_seg aligner) per ONU segment."""
        lay = self.lay
        nU = np.int64(lay.n_clients)
        nonzero = self.qb > 0.0
        if mask is not None:
            nonzero = nonzero & mask
        pk = np.where(nonzero, self.push_key, 0)
        combined = np.where(nonzero, pk * nU + lay.pos, _IKEY_INF)
        m = np.minimum.reduceat(combined, lay.seg_starts, axis=1)
        has = m < _IKEY_INF
        pos = np.where(has, m % nU, 0)
        return has, pos

    def hol_per_onu(self, mask: Optional[np.ndarray] = None
                    ) -> np.ndarray:
        lay = self.lay
        live = self.qb > 0.0
        if mask is not None:
            live = live & mask
        if lay.identity:
            return np.where(live, self.push_time, np.inf)
        out = np.full((self.B, self.N), np.inf)
        if self.single:
            out[:, lay.seg_onus] = np.where(
                live, self.push_time, np.inf
            )
            return out
        has, pos = self._heads(mask)
        times = np.where(
            has, self.push_time[self._bidx, pos], np.inf
        )
        out[:, lay.seg_onus] = times
        return out

    def serve(self, grants_onu: np.ndarray, backlog_onu: np.ndarray,
              mask: Optional[np.ndarray] = None):
        """Drain FIFO heads per ONU, reproducing ``OnuQueue.serve``'s
        1-bit segment compaction (which also charges the grant).

        With ``mask`` (multi-tenant jobs) the grant is one job's share
        and only that job's columns drain — ``backlog_onu`` must then
        be the same-masked per-ONU backlog."""
        lay = self.lay
        if self.single:
            budget = (grants_onu if lay.identity
                      else grants_onu[:, lay.onu])
            act = (budget > CAP_EPS) & (self.qb > 0.0)
            if mask is not None:
                act = act & mask
            take = np.where(act, np.minimum(budget, self.qb), 0.0)
            drop = act & (self.qb - take <= SEG_EPS)
            self.qb = np.where(drop, 0.0, self.qb - take)
            return
        full = (grants_onu > 0.0) & (grants_onu == backlog_onu)
        if np.any(full):
            zero = full[:, lay.onu]
            if mask is not None:
                zero = zero & mask
            self.qb = np.where(zero, 0.0, self.qb)
        budget = np.where(full, 0.0, grants_onu)[:, lay.seg_onus]
        while True:
            has, pos = self._heads(mask)
            srv = has & (budget > CAP_EPS)
            if not np.any(srv):
                break
            hq = self.qb[self._bidx, pos]
            take = np.where(srv, np.minimum(budget, hq), 0.0)
            resid = np.where(srv, hq - take, np.inf)
            drop = srv & (resid <= SEG_EPS)
            newq = np.where(drop, 0.0, hq - take)
            rb, rs = np.nonzero(srv)
            self.qb[rb, pos[rb, rs]] = newq[rb, rs]
            charge = np.where(drop, resid, 0.0)
            budget = np.maximum(budget - take - charge, 0.0)


def _credit(rem, done, done_t, drained, t_done: float):
    """Attribute served FL bits to the clients that own them.

    ``drained`` is each client's own queue drain this cycle
    (``qb_before - qb_after``) — ownership attribution, mirroring the
    reference's owner-tagged segments: a client is done exactly when its
    queued update has fully crossed the wire. (The segment-compaction
    charge zeroes a queue together with its sub-1-bit remnant, so
    "queue empty" and "remaining ≤ 1 bit" coincide on both backends.)
    """
    new_rem = rem - drained
    newly = ~done & (drained > 0.0) & (new_rem <= EPS_BITS)
    rem = np.where(newly, 0.0, np.maximum(new_rem, 0.0))
    done = done | newly
    done_t = np.where(newly, t_done, done_t)
    return rem, done, done_t


def _slot_grants(slot_arrays, backlog_onu, t: float, cyc: float,
                 cap: np.ndarray, n_onus: int) -> np.ndarray:
    """SlicedDBA slot grants: overlap * slice rate, capped by the FL
    backlog and the (sequentially spent) per-row cycle capacity
    ``cap`` — the wavelength capacity, or the row's waterfilled CPS
    share."""
    ts, te, onu_idx, rate, valid = slot_arrays
    B, S = ts.shape
    te_g = te + cyc
    active = valid & (ts < t + cyc) & (te_g > t)
    if not np.any(active):
        return np.zeros((B, n_onus))
    overlap = np.minimum(te_g, t + cyc) - np.maximum(ts, t)
    want = rate * np.maximum(overlap, 0.0)
    bidx = np.arange(B)[:, None]
    want = np.minimum(want, backlog_onu[bidx, onu_idx])
    want = np.where(active & (want > 0.0), want, 0.0)
    prefix = np.cumsum(want, axis=1)
    grants = np.minimum(
        want, np.maximum(cap[:, None] - (prefix - want), 0.0)
    )
    out = np.zeros((B, n_onus))
    np.add.at(out, (np.broadcast_to(bidx, (B, S)), onu_idx), grants)
    return out


def _job_grants_fcfs(fl: _FLQueues, ctx, cap_fl: np.ndarray, t: float):
    """Per-job FCFS grant plan: split the FL residual capacity across
    jobs by the fairness policy on per-job total backlog, then
    oldest-first waterfill each job's share over its own queues.

    Returns ``(mask, grants_onu, backlog_onu)`` triples, one per job.
    The inter-job split is per PON row — the CPS coupling stays at the
    (case, pon) level because background demand entangles the rows
    before jobs are distinguishable.
    """
    masks = ctx["masks"]
    bos = [fl.backlog_per_onu(m) for m in masks]
    demand = np.stack([bo.sum(axis=1) for bo in bos], axis=1)
    shares = job_fair_split(demand, cap_fl, ctx["fairness"],
                            weights=ctx["weights"],
                            slack=ctx["deadlines"] - t)
    return [
        (m, _waterfill(bos[j], functools.partial(fl.hol_per_onu, m),
                       shares[:, j]), bos[j])
        for j, m in enumerate(masks)
    ]


def _job_grants_bs(slot_arrays, fl: _FLQueues, ctx, t: float, cyc: float,
                   cap: np.ndarray, n_onus: int,
                   cps_cap: Optional[float], n_pons: int):
    """Per-job SlicedDBA grant plan.

    Slot wants are computed exactly like ``_slot_grants`` (overlap *
    slice rate, capped by the owning job's backlog at the slot's ONU),
    aggregated into per-(row, job) demand for the fairness split —
    re-capped by the CPS waterfill over the flattened ``(pon, job)``
    shares of each case when a CPS rate binds — and each job's slots
    then spend prefix room within the job's own share.
    """
    ts, te, onu_idx, rate, valid, sjob = slot_arrays
    B, S = ts.shape
    masks = ctx["masks"]
    J = len(masks)
    bos = [fl.backlog_per_onu(m) for m in masks]
    te_g = te + cyc
    active = valid & (ts < t + cyc) & (te_g > t)
    # best-effort tail: inter-job fairness / CPS re-capping can
    # throttle a job below its scheduled slice rate, leaving backlog
    # when its window closes; an expired slot keeps requesting at the
    # slice rate so contended bits drain instead of starving
    tail = valid & (te_g <= t)
    if not np.any(active | tail):
        zero = np.zeros((B, n_onus))
        return [(m, zero, bos[j]) for j, m in enumerate(masks)]
    overlap = np.minimum(te_g, t + cyc) - np.maximum(ts, t)
    want = np.where(active, rate * np.maximum(overlap, 0.0),
                    np.where(tail, rate * cyc, 0.0))
    bidx = np.arange(B)[:, None]
    want = np.minimum(want, np.stack(bos)[sjob, bidx, onu_idx])
    want = np.where(want > 0.0, want, 0.0)
    demand = np.stack(
        [np.where(sjob == j, want, 0.0).sum(axis=1) for j in range(J)],
        axis=1,
    )
    shares = job_fair_split(demand, cap, ctx["fairness"],
                            weights=ctx["weights"],
                            slack=ctx["deadlines"] - t)
    if cps_cap is not None:
        # the (case, pon, job) waterfill: a case's bs rows are its
        # n_pons consecutive rows, so reshaping shares pon-major /
        # job-minor puts each case's P*J slices in one waterfill row
        shares = cps_waterfill(
            shares.reshape(-1, n_pons * J), cps_cap
        ).reshape(B, J)
    plan = []
    for j, m in enumerate(masks):
        wj = np.where(sjob == j, want, 0.0)
        prefix = np.cumsum(wj, axis=1)
        gj = np.minimum(
            wj, np.maximum(shares[:, j:j + 1] - (prefix - wj), 0.0)
        )
        out = np.zeros((B, n_onus))
        np.add.at(out, (np.broadcast_to(bidx, (B, S)), onu_idx), gj)
        plan.append((m, out, bos[j]))
    return plan


# ---------------------------------------------------------------------------
# phase runner
# ---------------------------------------------------------------------------


def _run_phase(cfg, lay: _Layout, rem_init, ready_t,
               stream: Optional[_Stream], mode: str, slot_arrays=None,
               max_t: float = 600.0, fill_unfinished: bool = True,
               cap_row: Optional[np.ndarray] = None,
               cps_cap: Optional[float] = None, n_pons: int = 1,
               deadline_row: Optional[np.ndarray] = None,
               outage_row: Optional[np.ndarray] = None,
               collector=None, phase_label: str = "",
               jobs_ctx=None):
    """One transfer phase for a (policy-homogeneous) batch of rows.

    Rows are ``(case, pon)`` pairs (case-major); ``cap_row`` is each
    row's wavelength cycle capacity and ``cps_cap`` the per-cycle CPS
    budget shared by the ``n_pons`` consecutive rows of one case —
    when set, each cycle first waterfills the CPS capacity across a
    case's per-PON demands and every row allocates within its share.

    Returns ``(done_t, rem)``: per-client completion times
    ``(B, n_clients)`` (NaN for clients not in a case's workload) and
    the bits still unserved when the phase ended. With
    ``fill_unfinished`` (the legacy behaviour) clients cut off at
    ``max_t`` get ``t + propagation`` as their completion time; the
    timeline's deadline mode passes False and reads ``rem`` instead
    (missed-deadline bits defer to the next round). ``stream`` is the
    background arrival stream (unused — and may be None — in "bs"
    mode).

    ``deadline_row`` (``(B,)`` float, ``inf`` = no deadline) gives each
    row its OWN time cutoff: cycles starting at or past a row's
    deadline grant it nothing (exactly the scalar-deadline rule ``t <
    deadline``, applied per row), unfinished clients of deadlined rows
    keep their ``rem``, and ``inf`` rows fall back to the
    ``max_t``-capped ``fill_unfinished`` behaviour. All ``n_pons``
    rows of one case must share a deadline (the CPS waterfill couples
    them).

    ``outage_row`` (``(B, 2)`` float ``[start, end)``, ``inf`` rows =
    never) masks each row's capacity to zero for cycles starting
    inside its outage window (``start <= t < end`` on the cycle-start
    clock, exactly the deadline comparison): the ONU/link is dark —
    arrivals still queue, nothing is granted — and service resumes
    after the window. ``None`` is bitwise identical to all-``inf``.

    ``collector`` (``repro.obs.Collector``) turns on per-cycle metrics
    over the ``(B,)`` row axis — backlog depths, grant totals, cycle
    utilization, waterfill residuals, CPS want/eff — as a
    ``PhaseStats`` registered under ``phase_label``.  With
    ``collector=None`` the instrumentation is a single identity check
    per cycle and every output is bitwise unchanged: the accumulators
    only *read* arrays the phase already computed.

    ``jobs_ctx`` (multi-tenant sweeps) carries the per-row job masks,
    weights, deadlines and the fairness policy: each cycle's FL
    capacity is first split across jobs (``_job_grants_fcfs`` /
    ``_job_grants_bs``) and every job drains only its own queues
    within its share. ``None`` (single-tenant) keeps the grant/serve
    sequence bitwise unchanged.
    """
    B = rem_init.shape[0]
    N = cfg.n_onus
    cyc = cfg.cycle_time_s
    prop = cfg.propagation_s
    if cap_row is None:
        cap_row = np.full((B,), cfg.line_rate_bps * cyc * cfg.efficiency)
    cap_col = cap_row
    if deadline_row is None:
        cap_t = None
        tmax = max_t
    else:
        cap_t = np.where(np.isfinite(deadline_row), deadline_row, max_t)
        tmax = float(cap_t.max())

    rem = rem_init.copy()
    done = ~lay.part | (rem <= 0.0)
    done_t = np.full(rem.shape, np.nan)
    fl = _FLQueues(lay, B, N)
    # Under the Sliced DBA the FL slice is served *first*; background only
    # gets the residual capacity and never feeds back into the FL grants,
    # so the BS phase needs no background simulation at all (this is the
    # paper's isolation claim, and it is exact — not an approximation).
    use_bg = mode == "fcfs"
    bg = _BgQueues(B, N) if use_bg else None

    obs = None
    if collector is not None:
        obs = collector.phase(phase_label or mode, B)
        ob_bg_depth = ob_fl_depth = ob_bg_g = ob_fl_g = None
        ob_cps_w = ob_cps_e = None

    n_left = int(np.count_nonzero(~done & lay.part))
    waiting = lay.part & ~done
    n_wait = int(np.count_nonzero(waiting))
    t = 0.0
    k = 0
    cap_cyc = cap_col
    while t < tmax and n_left:
        if cap_t is not None:
            alive = cap_t > t
            if not np.any(alive[:, None] & lay.part & ~done):
                break
            cap_cyc = np.where(alive, cap_col, 0.0)
        if outage_row is not None:
            base = cap_cyc if cap_t is not None else cap_col
            dark = (outage_row[:, 0] <= t) & (t < outage_row[:, 1])
            cap_cyc = np.where(dark, 0.0, base)
        if use_bg:
            bg.push(k, stream.row(k))
        if n_wait:
            # a waiting client can't already be done (ownership credit
            # requires a pushed queue), so part & ~done is implied
            newly = waiting & (ready_t <= t + cyc)
            n_new = int(np.count_nonzero(newly))
            if n_new:
                waiting &= ~newly
                n_wait -= n_new
                fl.push(newly, rem, k, t, ready_t)

        # pushed & undone clients hold exactly the nonzero FL queues, so
        # the idle stretch before the first ready client skips FL work
        if n_left > n_wait:
            backlog_onu = fl.backlog_per_onu()
            if obs is not None:
                ob_fl_depth = backlog_onu.sum(axis=1)
                if use_bg:
                    ob_bg_depth = bg.backlog.sum(axis=1)
            plan = None
            if mode == "fcfs":
                if cps_cap is None:
                    eff = cap_cyc
                else:
                    want = np.minimum(
                        bg.backlog.sum(axis=1) + backlog_onu.sum(axis=1),
                        cap_cyc,
                    )
                    eff = cps_waterfill(
                        want.reshape(-1, n_pons), cps_cap
                    ).reshape(-1)
                    if obs is not None:
                        ob_cps_w, ob_cps_e = want, eff
                bg_grants = _waterfill(bg.backlog, bg.hol_key, eff)
                cap_fl = eff - bg_grants.sum(axis=1)
                if jobs_ctx is None:
                    fl_grants = _waterfill(
                        backlog_onu, fl.hol_per_onu, cap_fl
                    )
                else:
                    plan = _job_grants_fcfs(fl, jobs_ctx, cap_fl, t)
                    fl_grants = sum(g for _, g, _ in plan)
            elif jobs_ctx is None:
                fl_grants = _slot_grants(slot_arrays, backlog_onu, t,
                                         cyc, cap_cyc, N)
                if cps_cap is not None:
                    want = fl_grants.sum(axis=1)
                    eff = cps_waterfill(
                        want.reshape(-1, n_pons), cps_cap
                    ).reshape(-1)
                    if obs is not None:
                        ob_cps_w, ob_cps_e = want, eff
                    if np.any(eff < want):
                        fl_grants = _slot_grants(
                            slot_arrays, backlog_onu, t, cyc, eff, N
                        )
            else:
                plan = _job_grants_bs(slot_arrays, fl, jobs_ctx, t, cyc,
                                      cap_cyc, N, cps_cap, n_pons)
                fl_grants = sum(g for _, g, _ in plan)
            if obs is not None:
                ob_fl_g = fl_grants.sum(axis=1)
                if use_bg:
                    ob_bg_g = bg_grants.sum(axis=1)
            if use_bg:
                bg.serve(bg_grants, k)
            if np.any(fl_grants > 0.0):
                prev_qb = fl.qb.copy()
                if plan is None:
                    fl.serve(fl_grants, backlog_onu)
                else:
                    for mask_j, g_j, bo_j in plan:
                        if np.any(g_j > 0.0):
                            fl.serve(g_j, bo_j, mask_j)
                rem, done, done_t = _credit(
                    rem, done, done_t, prev_qb - fl.qb, t + cyc + prop
                )
                n_left = int(np.count_nonzero(~done & lay.part))
        elif use_bg:
            if cps_cap is None:
                eff = cap_cyc
            else:
                want = np.minimum(bg.backlog.sum(axis=1), cap_cyc)
                eff = cps_waterfill(
                    want.reshape(-1, n_pons), cps_cap
                ).reshape(-1)
                if obs is not None:
                    ob_cps_w, ob_cps_e = want, eff
            bg_grants = _waterfill(bg.backlog, bg.hol_key, eff)
            if obs is not None:
                ob_bg_depth = bg.backlog.sum(axis=1)
                ob_bg_g = bg_grants.sum(axis=1)
            bg.serve(bg_grants, k)
        if obs is not None:
            obs.cycle(cap_cyc, bg_backlog=ob_bg_depth,
                      fl_backlog=ob_fl_depth, bg_grants=ob_bg_g,
                      fl_grants=ob_fl_g, cps_want=ob_cps_w,
                      cps_eff=ob_cps_e)
            ob_bg_depth = ob_fl_depth = ob_bg_g = ob_fl_g = None
            ob_cps_w = ob_cps_e = None
        t += cyc
        k += 1

    if cap_t is not None:
        # per-row deadlines: only deadline-free (inf) rows time out at
        # ``max_t`` with filled completion times; deadlined rows report
        # their unserved ``rem`` instead
        left = lay.part & ~done & ~np.isfinite(deadline_row)[:, None]
        done_t = np.where(left, t + prop, done_t)
    elif fill_unfinished:
        left = lay.part & ~done
        done_t = np.where(left, t + prop, done_t)
    return done_t, rem


# ---------------------------------------------------------------------------
# sweep driver
# ---------------------------------------------------------------------------


def _case_bg_rate(case: SweepCase, cfg, t_round_hint: float) -> float:
    clients = case.workload.clients
    n = len(clients)
    training_rate = (
        n * (case.workload.model_bits
             + float(np.mean([c.m_ud_bits for c in clients])))
        / max(t_round_hint, 1e-9)
    )
    return background_rate_for_load(
        case.load, cfg.line_rate_bps, training_rate
    )


@functools.lru_cache(maxsize=512)
def _bs_slice_cached(profiles: tuple, capacity_bps: float):
    if not profiles:
        return None, slots_to_arrays([])
    spec = compute_slice(
        list(profiles), t_current=0.0, t_round=0.0,
        capacity_bps=capacity_bps, h=1,
    )
    slots = schedule_slots(list(profiles), spec, round_start=0.0)
    return spec, slots_to_arrays(slots)


def _bs_slice(profiles: List[ClientProfile], capacity_bps: float):
    """Per-segment slice spec + slot arrays (empty segments allowed —
    a PON row of a multi-PON case may hold no clients).

    Memoized on the (frozen, hashable) profile tuple: the bs downstream
    is analytic, so a folded/sequential timeline re-derives the *same*
    slice spec and slot schedule every round — the profile shows the
    repeated ``compute_slice``/``schedule_slots``/``slots_to_arrays``
    work on every phase entry.  Callers treat the returned arrays as
    immutable (``_stack_slots`` only reads them).
    """
    try:
        return _bs_slice_cached(tuple(profiles), float(capacity_bps))
    except TypeError:             # unhashable profile type: uncached
        return _bs_slice_cached.__wrapped__(
            tuple(profiles), float(capacity_bps))


def _stack_slots(per_row, n_onus: int):
    """Pad per-row slot arrays to a common (B, S) shape."""
    S = max(
        (len(a["client_id"]) for _, a in per_row), default=0
    ) or 1
    B = len(per_row)
    ts = np.full((B, S), np.inf)
    te = np.full((B, S), -np.inf)
    onu = np.zeros((B, S), np.int64)
    rate = np.zeros((B, 1))
    valid = np.zeros((B, S), bool)
    for b, (spec, a) in enumerate(per_row):
        s = len(a["client_id"])
        if s:
            ts[b, :s] = a["t_start"]
            te[b, :s] = a["t_end"]
            onu[b, :s] = a["client_id"] % n_onus
            valid[b, :s] = True
        if spec is not None:
            rate[b, 0] = spec.bandwidth_bps
    return ts, te, onu, rate, valid


def _stack_slots_jobs(per_row, n_onus: int):
    """Pad per-(row, job) slot arrays to a common ``(B, S)`` shape.

    ``per_row[b]`` is a list of ``(job_index, spec, arrays)`` triples
    in job order. Unlike ``_stack_slots``, ``rate`` is per-slot — each
    job carves its own slice, so one row holds several bandwidths —
    and ``sjob`` binds every slot to its owning job (padding binds to
    job 0 with ``valid`` False, contributing zero demand).
    """
    B = len(per_row)
    S = max(
        (sum(len(a["client_id"]) for _, _, a in row) for row in per_row),
        default=0,
    ) or 1
    ts = np.full((B, S), np.inf)
    te = np.full((B, S), -np.inf)
    onu = np.zeros((B, S), np.int64)
    rate = np.zeros((B, S))
    valid = np.zeros((B, S), bool)
    sjob = np.zeros((B, S), np.int64)
    for b, row in enumerate(per_row):
        s0 = 0
        for j, spec, a in row:
            s = len(a["client_id"])
            if not s:
                continue
            ts[b, s0:s0 + s] = a["t_start"]
            te[b, s0:s0 + s] = a["t_end"]
            onu[b, s0:s0 + s] = a["client_id"] % n_onus
            rate[b, s0:s0 + s] = spec.bandwidth_bps
            valid[b, s0:s0 + s] = True
            sjob[b, s0:s0 + s] = j
            s0 += s
    return ts, te, onu, rate, valid, sjob


def _sweep_topology(cases: Sequence[SweepCase]) -> MultiPonTopology:
    """The one topology shared by every case (None ≡ trivial)."""
    topos = {case.topology for case in cases}
    topos.discard(None)
    if len(topos) > 1:
        raise ValueError("sweep cases must share one MultiPonTopology")
    if not topos:
        return MultiPonTopology()
    topo = topos.pop()
    if any(case.topology is None for case in cases) and not topo.trivial:
        raise ValueError("sweep cases must share one MultiPonTopology")
    return topo


def _check_jobs_cases(cases: Sequence[SweepCase]):
    """Every case carries jobs partitioning its workload, or none do."""
    for b, case in enumerate(cases):
        if case.jobs is None:
            raise ValueError(
                f"cases[{b}] has no jobs but the sweep carries jobs; "
                "give every case a jobs tuple (or none)"
            )
        try:
            validate_case_jobs(case.jobs, case.workload)
        except ValueError as e:
            raise ValueError(f"cases[{b}]: {e}") from None


def _multi_job_fairness(cases: Sequence[SweepCase], ul_deadline_s,
                        ul_outage_s) -> str:
    """Validate a genuinely multi-tenant sweep; returns its fairness."""
    if ul_deadline_s is not None or ul_outage_s is not None:
        raise ValueError(
            "multi-job sweeps take per-job deadlines "
            "(JobSpec.deadline_s under fairness='deadline'), not "
            "round-level ul_deadline_s/ul_outage_s"
        )
    fair = {case.fairness for case in cases}
    if len(fair) != 1:
        raise ValueError(
            f"sweep cases must share one fairness policy; "
            f"got {sorted(fair)}"
        )
    fairness = fair.pop()
    if fairness not in FAIRNESS_POLICIES:
        raise ValueError(
            f"unknown fairness policy {fairness!r}; "
            f"have {FAIRNESS_POLICIES}"
        )
    for b, case in enumerate(cases):
        if case.dl_arrivals is not None or case.ul_arrivals is not None:
            raise ValueError(
                f"cases[{b}]: injected arrivals are a single-tenant "
                "parity hook; multi-job cases draw counter streams"
            )
        if case.no_dl_ids:
            raise ValueError(
                f"cases[{b}]: no_dl_ids (deadline carriers) do not "
                "compose with multi-job cases"
            )
    return fairness


def _record_job_uploads(collector, case: SweepCase, res):
    """Per-job upload-time recording (``<policy>/job<id>`` keys)."""
    if collector is None or not res.job_stats:
        return
    ul = res.ul_done
    for job in case.jobs:
        times = [
            ul[cid] for cid in job.clients
            if cid in ul and np.isfinite(ul[cid])
        ]
        if times:
            collector.record_upload_times(
                f"{case.policy}/job{job.job_id}", case.load, times
            )


def _single_job_sweep(cfg, cases: Sequence[SweepCase], **kw):
    """Degenerate jobs sweeps — every case has exactly one job — run on
    the single-tenant path (bitwise identical to a no-jobs sweep of the
    same workloads, preserving the PR 8 pins) and get their
    ``job_stats`` attached post-hoc."""
    from repro.net.sim import FLRoundWorkload

    norm = []
    for case in cases:
        job = case.jobs[0]
        wl = case.workload
        if float(job.model_bits) != float(wl.model_bits):
            wl = FLRoundWorkload(
                clients=wl.clients, model_bits=float(job.model_bits),
                t_aggregate=wl.t_aggregate,
            )
        norm.append(replace(case, jobs=None, workload=wl))
    results = _round_sweep(cfg, norm, **kw)
    topo = _sweep_topology(list(cases))
    for case, res in zip(cases, results):
        res.job_stats = compute_job_stats(
            case.jobs, res.ul_done, cfg.n_onus, topo.n_pons
        )
        _record_job_uploads(kw.get("collector"), case, res)
    return results


def _round_sweep(cfg, cases: Sequence[SweepCase],
                 t_round_hint: float = 10.0,
                 max_t: float = 600.0,
                 ul_deadline_s=None,
                 ul_outage_s=None,
                 collector=None,
                 backend: Optional[str] = None,
                 ) -> List["RoundResult"]:
    """Simulate every sweep case as one stacked array simulation.

    Semantics match ``repro.net.sim.simulate_round``'s reference
    implementation per case (property-tested); both backends consume the
    same counter-based arrival stream keyed by (seed, phase,
    stream_round, pon), so seeded results agree across backends and
    batch compositions unless arrivals are injected.

    A shared ``SweepCase.topology`` stacks every case over its
    ``n_pons`` wavelength segments: the simulation rows become
    ``(case, pon)`` pairs over per-PON ONU columns, each row under its
    own wavelength capacity, coupled per cycle by the CPS waterfill
    (``repro.net.multi_pon``) when the topology carries a CPS rate.
    With injected arrival matrices the columns are global ONUs
    (``n_pons * cfg.n_onus`` wide) and each row replays its own PON's
    slice.

    ``ul_deadline_s`` cuts the upload phase at a round deadline: clients
    still transmitting then keep their unserved bits in the result's
    ``ul_remaining`` (their ``ul_done`` is NaN) — the multi-round
    timeline defers those bits to the next round. A scalar applies to
    every case (the PR 3 behaviour, bitwise unchanged); a sequence
    gives each case its OWN deadline (``None``/``inf`` entries =
    no deadline for that case) — the timeline's folded drop/partial
    rows and the async mode's per-case k-th-completion cutoffs.

    ``ul_outage_s`` injects per-case upstream ONU/link outage windows
    (``repro.faults``): a sequence of ``None`` (no outage), ``(2,)``
    ``[start, end)`` (every PON of the case), or ``(n_pons, 2)``
    per-PON windows, phase-relative seconds like the deadlines. During
    a window the affected rows' cycle capacity is masked to zero (the
    link is dark; arrivals still queue) — one more per-row array axis,
    exactly like the per-case deadline column. ``None`` (the default)
    is bitwise identical to all-``inf`` windows.

    ``collector`` (``repro.obs.Collector``, optional) records per-phase
    cycle metrics inside ``_run_phase`` plus per-case upload-completion
    times keyed by (policy, load); ``collector=None`` (the default) is
    bitwise identical to an uninstrumented run.

    ``backend`` selects the phase engine: ``None``/``"numpy"`` is the
    host cycle loop (the default, bitwise-pinned); ``"jit"`` compiles
    each phase to one jax device program with the traffic sampler fused
    in (``repro.kernels.ponsim``) — parity with numpy at rtol 1e-6,
    with a numpy re-run, counted in ``ops.host_fallbacks()``, for phases
    whose background state outgrows the device ring (see
    ``ops.run_phase_device``).  The jit
    backend rejects injected arrival matrices and ``collector``
    instrumentation.
    """
    from repro.net.sim import RoundResult  # lazy: sim imports us lazily
    from repro.obs.trace import maybe_span

    cases = list(cases)
    if backend not in (None, "numpy", "jit"):
        raise ValueError(f"unknown engine backend {backend!r}")
    use_jit = backend == "jit"
    if use_jit:
        if collector is not None:
            raise ValueError(
                "backend='jit' does not support collector "
                "instrumentation; use the numpy backend"
            )
        if any(case.dl_arrivals is not None
               or case.ul_arrivals is not None for case in cases):
            raise ValueError(
                "backend='jit' does not support injected arrival "
                "matrices; use the numpy backend"
            )
    jobs_any = any(case.jobs is not None for case in cases)
    fairness = None
    if jobs_any:
        _check_jobs_cases(cases)
        if not any(len(case.jobs) > 1 for case in cases):
            return _single_job_sweep(
                cfg, cases, t_round_hint=t_round_hint, max_t=max_t,
                ul_deadline_s=ul_deadline_s, ul_outage_s=ul_outage_s,
                collector=collector, backend=backend,
            )
        fairness = _multi_job_fairness(cases, ul_deadline_s, ul_outage_s)
        if use_jit:
            # kernels/ponsim carries no job axis: multi-job sweeps run
            # on the numpy engine, counted as a host fallback (DESIGN §12)
            from repro.kernels.ponsim.ops import note_host_fallback

            note_host_fallback("multi_job")
            use_jit = False
    topo = _sweep_topology(cases)
    P = topo.n_pons
    n_local = cfg.n_onus
    total_onus = P * n_local
    for b, case in enumerate(cases):
        if case.policy not in ("fcfs", "bs"):
            raise ValueError(f"unknown policy {case.policy!r}")
        if case.policy == "bs":
            bad = [c.client_id for c in case.workload.clients
                   if c.client_id >= total_onus]
            if bad:
                raise ValueError(
                    "bs policy requires client_id < n_onus * n_pons; "
                    f"got {bad}"
                )
        for name in ("dl_arrivals", "ul_arrivals"):
            arr = getattr(case, name)
            if arr is None:
                continue
            a = np.asarray(arr, np.float64)
            if a.ndim != 2 or a.shape[1] != total_onus:
                raise ValueError(
                    f"cases[{b}].{name} must be 2-D with "
                    f"n_pons * n_onus = {total_onus} columns; "
                    f"got shape {np.shape(arr)}"
                )
    lay = _layout_for(cases, n_local, P)
    B = len(cases)
    R = B * P
    row_case = np.repeat(np.arange(B), P)
    row_pon = np.tile(np.arange(P), B)
    rates_pon = topo.rates(cfg)
    cap_row = np.tile(topo.capacity_bits(cfg), B)
    cps_cap = topo.cps_capacity_bits(cfg)
    per_onu_rate = np.stack([
        pon_bg_rates(c.workload.clients, c.workload.model_bits, c.load,
                     cfg, topo, t_round_hint,
                     model_bits_by_client=(
                         None if c.jobs is None else
                         {cid: float(job.model_bits)
                          for job in c.jobs for cid in job.clients}
                     ))
        for c in cases
    ])                                                  # (B, n_pons)
    per_case_dl = isinstance(ul_deadline_s, (list, tuple, np.ndarray))
    if per_case_dl:
        dl_case = np.array(
            [np.inf if d is None else float(d) for d in ul_deadline_s],
            np.float64,
        )
        if dl_case.shape != (B,):
            raise ValueError(
                f"per-case ul_deadline_s needs {B} entries; "
                f"got shape {dl_case.shape}"
            )
        dl_row = np.repeat(dl_case, P)
        ul_max_t = max_t
    else:
        dl_case = dl_row = None
        ul_max_t = max_t if ul_deadline_s is None else ul_deadline_s
    if ul_outage_s is not None:
        if len(ul_outage_s) != B:
            raise ValueError(
                f"per-case ul_outage_s needs {B} entries; "
                f"got {len(ul_outage_s)}"
            )
        outage_row = np.full((B, P, 2), np.inf)
        for b, win in enumerate(ul_outage_s):
            if win is None:
                continue
            arr = np.asarray(win, np.float64)
            if arr.shape == (2,):
                arr = np.broadcast_to(arr, (P, 2))
            if arr.shape != (P, 2):
                raise ValueError(
                    f"ul_outage_s[{b}] must be (2,) or ({P}, 2); "
                    f"got shape {arr.shape}"
                )
            outage_row[b] = arr
        outage_row = outage_row.reshape(R, 2)
        if not np.isfinite(outage_row[:, 0]).any():
            outage_row = None       # all-inf: keep the bitwise-off path
    else:
        outage_row = None
    no_dl = np.zeros((R, lay.n_clients), bool)
    for b, case in enumerate(cases):
        if case.no_dl_ids:
            skip = list(case.no_dl_ids)
            for p in range(P):
                no_dl[b * P + p] = np.isin(lay.cid_of[p], skip)
    no_dl &= lay.part

    # multi-tenant jobs: the per-row job axis next to the slot layout —
    # every live column binds to its owning job (jcol), carries its
    # job's model bits (mb), and every row knows its jobs' weights and
    # soft deadlines for the fairness split. Sweeps mixing job counts
    # pad to the max J with zero-demand phantom jobs, which every
    # fairness policy grants nothing.
    jobs_info = None
    if jobs_any:
        J = max(len(case.jobs) for case in cases)
        jcol = np.full((R, lay.n_clients), -1, np.int64)
        mb_col = np.zeros((R, lay.n_clients))
        w_row = np.ones((R, J))
        dl_jrow = np.full((R, J), np.inf)
        for b, case in enumerate(cases):
            jidx_of = {cid: j for j, job in enumerate(case.jobs)
                       for cid in job.clients}
            mb_of = {cid: float(job.model_bits) for job in case.jobs
                     for cid in job.clients}
            for p in range(P):
                r = b * P + p
                for col in np.nonzero(lay.part[r])[0]:
                    cid = int(lay.cid_of[p, col])
                    jcol[r, col] = jidx_of[cid]
                    mb_col[r, col] = mb_of[cid]
            for j, job in enumerate(case.jobs):
                w_row[b * P:(b + 1) * P, j] = float(job.weight)
                if job.deadline_s is not None:
                    dl_jrow[b * P:(b + 1) * P, j] = float(job.deadline_s)
        jobs_info = {"J": J, "jcol": jcol, "mb": mb_col, "w": w_row,
                     "dl": dl_jrow, "fairness": fairness}

    def jobs_ctx_for(sel):
        """Row-sliced per-job phase context (None when single-tenant)."""
        if jobs_info is None:
            return None
        jc = jobs_info["jcol"][sel]
        return {
            "masks": [jc == j for j in range(jobs_info["J"])],
            "weights": jobs_info["w"][sel],
            "deadlines": jobs_info["dl"][sel],
            "fairness": jobs_info["fairness"],
        }

    def providers(sel, phase):
        from repro.kernels.traffic.ops import make_stream_key

        entries = []
        for r in sel:
            b, p = int(row_case[r]), int(row_pon[r])
            case = cases[b]
            injected = (case.dl_arrivals if phase == "dl"
                        else case.ul_arrivals)
            if injected is not None:
                if P > 1:
                    arr = np.asarray(injected, np.float64)
                    if arr.ndim != 2 or arr.shape[1] != total_onus:
                        raise ValueError(
                            f"arrivals must be (n_cycles, {total_onus})"
                        )
                    injected = arr[:, p * n_local:(p + 1) * n_local]
                entries.append(_CaseFixed(injected, n_local))
            else:
                entries.append((
                    make_stream_key(case.seed, 0 if phase == "dl" else 1,
                                    case.stream_round, p),
                    burst_lambda(per_onu_rate[b, p], cfg.cycle_time_s,
                                 PACKET_BITS, cfg.bg_burst_packets),
                ))
        return _Stream(entries, n_local, 1.0 / cfg.bg_burst_packets)

    def stream_params(sel, phase):
        """Raw (keys, lams) of ``providers(sel, phase)``'s sampled
        entries — the jit backend fuses the sampler on-device instead
        of going through a host ``_Stream``."""
        from repro.kernels.traffic.ops import make_stream_key

        ks = np.empty((len(sel), 2), np.uint32)
        ls = np.empty((len(sel),), np.float32)
        for i, r in enumerate(sel):
            b, p = int(row_case[r]), int(row_pon[r])
            case = cases[b]
            ks[i] = make_stream_key(case.seed, 0 if phase == "dl" else 1,
                                    case.stream_round, p)
            ls[i] = burst_lambda(per_onu_rate[b, p], cfg.cycle_time_s,
                                 PACKET_BITS, cfg.bg_burst_packets)
        return ks, ls

    def run_phase(sub, rem0, ready, sel, phase, mode, **kw):
        """One phase on the selected backend; the jit path falls back
        to numpy, counted, when the device program reports an inexact
        bg walk."""
        if use_jit:
            from repro.kernels.ponsim.ops import (note_host_fallback,
                                                  run_phase_device)

            keys_ = lams_ = None
            if mode == "fcfs":
                keys_, lams_ = stream_params(sel, phase)
            out = run_phase_device(
                cfg, sub, rem0, ready, mode, keys=keys_, lams=lams_,
                slot_arrays=kw.get("slot_arrays"), max_t=kw["max_t"],
                fill_unfinished=kw.get("fill_unfinished", True),
                cap_row=kw.get("cap_row"), cps_cap=kw.get("cps_cap"),
                n_pons=kw.get("n_pons", 1),
                deadline_row=kw.get("deadline_row"),
                outage_row=kw.get("outage_row"),
            )
            if out is not None:
                return out
            note_host_fallback("ring_overflow")
        stream = providers(sel, phase) if mode == "fcfs" else None
        return _run_phase(cfg, sub, rem0, ready, stream, mode, **kw)

    # ---- downstream ------------------------------------------------------
    dl_done = np.full((R, lay.n_clients), np.nan)
    fcfs_rows = np.array(
        [r for r in range(R) if cases[row_case[r]].policy == "fcfs"],
        np.int64,
    )
    bs_rows = np.array(
        [r for r in range(R) if cases[row_case[r]].policy == "bs"],
        np.int64,
    )
    if len(fcfs_rows):
        sub = lay.rows(fcfs_rows)
        if jobs_info is None:
            bits = np.array([cases[row_case[r]].workload.model_bits
                             for r in fcfs_rows])[:, None]
        else:
            bits = jobs_info["mb"][fcfs_rows]
        rem0 = np.where(sub.part & ~no_dl[fcfs_rows], bits, 0.0)
        ready0 = np.zeros_like(rem0)
        with maybe_span(collector, "phase:dl:fcfs", rows=len(fcfs_rows)):
            dl_done[fcfs_rows], _ = run_phase(
                sub, rem0, ready0, fcfs_rows, "dl", "fcfs",
                max_t=max_t, cap_row=cap_row[fcfs_rows], cps_cap=cps_cap,
                n_pons=P, collector=collector, phase_label="dl:fcfs",
                jobs_ctx=jobs_ctx_for(fcfs_rows),
            )
    for r in bs_rows:
        b, p = int(row_case[r]), int(row_pon[r])
        mb = (cases[b].workload.model_bits if jobs_info is None
              else jobs_info["mb"][r])
        t_bcast = mb / (rates_pon[p] * cfg.efficiency) + cfg.propagation_s
        dl_done[r] = np.where(lay.part[r], t_bcast, np.nan)
    dl_done = np.where(no_dl, 0.0, dl_done)

    ready_t = dl_done + lay.t_ud

    # ---- upstream --------------------------------------------------------
    ul_done = np.full((R, lay.n_clients), np.nan)
    ul_rem = np.zeros((R, lay.n_clients))
    specs: Dict[int, SliceSpec] = {}
    if len(fcfs_rows):
        sub = lay.rows(fcfs_rows)
        rem0 = np.where(sub.part, sub.m_ud, 0.0)
        ready = np.where(sub.part, ready_t[fcfs_rows], np.inf)
        with maybe_span(collector, "phase:ul:fcfs", rows=len(fcfs_rows)):
            ul_done[fcfs_rows], ul_rem[fcfs_rows] = run_phase(
                sub, rem0, ready, fcfs_rows, "ul", "fcfs",
                max_t=ul_max_t, fill_unfinished=ul_deadline_s is None,
                cap_row=cap_row[fcfs_rows], cps_cap=cps_cap, n_pons=P,
                deadline_row=None if dl_row is None else dl_row[fcfs_rows],
                outage_row=(None if outage_row is None
                            else outage_row[fcfs_rows]),
                collector=collector, phase_label="ul:fcfs",
                jobs_ctx=jobs_ctx_for(fcfs_rows),
            )
    if len(bs_rows):
        per_row = []
        per_row_jobs = []
        for r in bs_rows:
            b, p = int(row_case[r]), int(row_pon[r])
            dl_map = {
                int(lay.cid_of[p, j]): float(dl_done[r, j])
                for j in range(lay.n_clients) if lay.part[r, j]
            }
            if jobs_info is None:
                profiles = [
                    ClientProfile(
                        client_id=c.client_id,
                        t_ud=c.t_ud,
                        t_dl=dl_map[c.client_id],
                        m_ud_bits=c.m_ud_bits,
                        distance_m=c.distance_m,
                    )
                    for c in cases[b].workload.clients
                    if c.client_id in dl_map
                ]
                spec, arrays = _bs_slice(
                    profiles, float(rates_pon[p] * cfg.efficiency)
                )
                if P == 1:
                    specs[b] = spec
                per_row.append((spec, arrays))
            else:
                # each job carves its own slice over its own clients;
                # slots stay grouped job-major, matching the oracle
                row_slots = []
                for j, job in enumerate(cases[b].jobs):
                    jset = set(job.clients)
                    profiles = [
                        ClientProfile(
                            client_id=c.client_id,
                            t_ud=c.t_ud,
                            t_dl=dl_map[c.client_id],
                            m_ud_bits=c.m_ud_bits,
                            distance_m=c.distance_m,
                        )
                        for c in cases[b].workload.clients
                        if c.client_id in dl_map and c.client_id in jset
                    ]
                    spec, arrays = _bs_slice(
                        profiles, float(rates_pon[p] * cfg.efficiency)
                    )
                    row_slots.append((j, spec, arrays))
                per_row_jobs.append(row_slots)
        slot_arrays = (_stack_slots(per_row, n_local)
                       if jobs_info is None
                       else _stack_slots_jobs(per_row_jobs, n_local))
        sub = lay.rows(bs_rows)
        rem0 = np.where(sub.part, sub.m_ud, 0.0)
        ready = np.where(sub.part, ready_t[bs_rows], np.inf)
        with maybe_span(collector, "phase:ul:bs", rows=len(bs_rows)):
            ul_done[bs_rows], ul_rem[bs_rows] = run_phase(
                sub, rem0, ready, bs_rows, "ul", "bs",
                slot_arrays=slot_arrays, max_t=ul_max_t,
                fill_unfinished=ul_deadline_s is None,
                cap_row=cap_row[bs_rows], cps_cap=cps_cap, n_pons=P,
                deadline_row=None if dl_row is None else dl_row[bs_rows],
                outage_row=(None if outage_row is None
                            else outage_row[bs_rows]),
                collector=collector, phase_label="ul:bs",
                jobs_ctx=jobs_ctx_for(bs_rows),
            )

    # ---- assemble --------------------------------------------------------
    results = []
    for b, case in enumerate(cases):
        dl: Dict[int, float] = {}
        rd: Dict[int, float] = {}
        ul: Dict[int, float] = {}
        remaining: Dict[int, float] = {}
        for p in range(P):
            r = b * P + p
            sel = lay.part[r]
            if not sel.any():
                continue
            ids = lay.cid_of[p][sel]
            dl.update(
                (int(i), float(v)) for i, v in zip(ids, dl_done[r, sel])
            )
            rd.update(
                (int(i), float(v)) for i, v in zip(ids, ready_t[r, sel])
            )
            ul.update(
                (int(i), float(v)) for i, v in zip(ids, ul_done[r, sel])
            )
            remaining.update(
                (int(i), float(v))
                for i, v in zip(ids, ul_rem[r, sel]) if v > 0.0
            )
        if per_case_dl:
            dlb = float(dl_case[b])
            has_dl = bool(np.isfinite(dl_case[b]))
        else:
            dlb = ul_deadline_s
            has_dl = ul_deadline_s is not None
        if remaining and has_dl:
            sync = dlb + case.workload.t_aggregate
        else:
            sync = max(ul.values()) + case.workload.t_aggregate
        if collector is not None:
            ul_times = [v for v in ul.values() if np.isfinite(v)]
            if ul_times:
                collector.record_upload_times(case.policy, case.load,
                                              ul_times)
        results.append(RoundResult(
            policy=case.policy,
            sync_time=sync,
            dl_done=dl,
            ready=rd,
            ul_done=ul,
            compute_bound=max(rd.values()),
            load=case.load,
            slice_spec=specs.get(b),
            ul_remaining=remaining if has_dl else None,
            job_stats=(None if case.jobs is None else
                       compute_job_stats(case.jobs, ul, n_local, P)),
        ))
        if case.jobs is not None:
            _record_job_uploads(collector, case, results[-1])
    return results


def simulate_round_sweep(cfg, cases=None,
                         t_round_hint: float = 10.0,
                         max_t: float = 600.0,
                         ul_deadline_s=None,
                         ul_outage_s=None,
                         collector=None,
                         backend: Optional[str] = None,
                         ) -> List["RoundResult"]:
    """Public round-sweep entry point.

    Preferred form: pass a ``repro.net.SweepSpec`` —
    ``simulate_round_sweep(spec)`` or ``simulate_round_sweep(cfg,
    spec)`` with an explicit ``PONConfig`` — which validates the bundle
    once and dispatches to the engine (``repro.net.api.simulate`` is
    the same call). The spec must not carry a ``schedule``; timelines
    go through ``simulate_timeline_sweep``/``simulate``.

    The legacy kwarg form ``simulate_round_sweep(cfg, cases,
    t_round_hint=..., ul_deadline_s=..., ...)`` still works, emits a
    ``DeprecationWarning``, and delegates to the same engine —
    results are identical (asserted in ``tests/test_api.py``). See
    ``_round_sweep`` for the full semantics of every knob.
    """
    from repro.net.api import SweepSpec, simulate

    spec = None
    pon = None
    if isinstance(cfg, SweepSpec):
        if cases is not None:
            raise TypeError(
                "simulate_round_sweep(spec) takes no second argument; "
                "put the PONConfig in spec.pon or call "
                "simulate_round_sweep(cfg, spec)"
            )
        spec = cfg
    elif isinstance(cases, SweepSpec):
        spec, pon = cases, cfg
    if spec is not None:
        if spec.schedule is not None:
            raise ValueError(
                "spec carries a schedule; call simulate(spec) or "
                "simulate_timeline_sweep(spec) for timelines"
            )
        return simulate(spec, pon, collector=collector)
    warnings.warn(
        "simulate_round_sweep(cfg, cases, **kwargs) is deprecated; "
        "build a repro.net.SweepSpec and call simulate(spec) "
        "(or pass the spec to simulate_round_sweep)",
        DeprecationWarning, stacklevel=2,
    )
    return _round_sweep(
        cfg, cases, t_round_hint=t_round_hint, max_t=max_t,
        ul_deadline_s=ul_deadline_s, ul_outage_s=ul_outage_s,
        collector=collector, backend=backend,
    )
