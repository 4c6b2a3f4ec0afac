"""Multi-round timeline engine over the batched PON round engine.

The paper's headline quantities (Fig. 3 training-time saving,
accuracy-vs-wall-clock) are *multi-round*: R synchronisation rounds back
to back, with elastic client membership and (optionally) per-round
deadlines. After PR 2 the co-simulation still drove the vectorized
engine one round at a time from a Python loop, rebuilding layout and
queue state every round. This module advances the whole training
timeline in one call:

* **Folded mode** (rounds independent given their start times): the
  round axis folds into the engine's batch axis — all R rounds of all
  B cases run as ONE stacked simulation. One ``_Layout`` build, one
  ``_BgQueues``/``_FLQueues`` allocation carried across the whole
  timeline, one cycle loop whose per-cycle Python cost is amortised
  over R·B rows instead of B. The counter-based arrival sampler
  (``repro.kernels.traffic``) keys round ``r``'s stream by
  ``(seed, phase, r)``, so every row addresses its own arrivals with no
  sequential draw state. Legal whenever nothing couples consecutive
  rounds: no deadline at all, or ``deadline_policy`` in
  ``{"drop", "partial"}`` (a straggler's unserved bits never cross the
  round boundary — folded rows carry per-row deadlines).
* **Sequential mode** (``deadline_policy="defer"``): a client still
  uploading at the deadline *defers* its remaining update bits to the
  next round (it skips the next model download and resumes the stale
  upload — array state carried between rounds), which couples
  consecutive rounds; the engine then advances round by round, still
  batched over cases.
* **Async mode** (``buffer_k``, FedBuff semantics): there is no fixed
  deadline — aggregation fires as soon as ``buffer_k`` pending uploads
  complete. Each round runs twice on the engine: a free-running pass
  finds the k-th completion time ``t_k`` (causality makes the prefix
  before ``t_k`` identical with or without a cutoff), then a deadline
  pass at ``t_k`` yields the exact unserved bits of the stragglers,
  which defer FedBuff-style. Per-client *staleness* ``τ_i`` (rounds
  elapsed since the client downloaded its model) is reported per round
  so the learning layer can weight stale updates (e.g. ``1/sqrt(1+τ)``).

Deadline policies (``TimelineSchedule.deadline_policy``):

* ``"defer"`` (default, the PR 3/4 behaviour — bitwise unchanged): the
  straggler keeps its unserved bits and resumes next round as a
  zero-compute carrier.
* ``"drop"``: the straggler's unserved bits are discarded at the
  deadline (its served bits were wasted wire time); the client
  re-enters fresh next round.
* ``"partial"``: the *served* fraction counts as a usable partial
  update (``TimelineRound.partial`` maps client → served fraction);
  the unserved remainder is discarded and the client re-enters fresh.

``simulate_timeline_reference`` is the parity oracle: an explicit
per-round Python loop over the *cycle-by-cycle dict simulator*
(``repro.net.sim.simulate_round_reference``), fed the engine's exact
counter streams via ``repro.net.traffic.CounterStream`` — extended
with the same two-pass rule for async rounds and the same policy
folding. Tests require sync times, per-round served bits, staleness
and policy outcomes to agree at rtol 1e-6 for all three policies and
async arrivals, including elastic membership and multi-PON topologies.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.faults import FaultSchedule, RetryPolicy
from repro.net.engine import SweepCase, _round_sweep
from repro.net.sim import FLRoundWorkload, RoundResult

__all__ = [
    "DEADLINE_POLICIES",
    "TimelineSchedule",
    "TimelineRound",
    "TimelineResult",
    "simulate_timeline_reference",
]

DEADLINE_POLICIES = ("defer", "drop", "partial")


@dataclass(frozen=True)
class TimelineSchedule:
    """The multi-round structure shared by every case of a sweep.

    ``membership``: optional ``(n_rounds, n_clients)`` bool mask over
    each case's ``workload.clients`` *list positions* — a client masked
    out of round r takes no part in it (downloads nothing, uploads no
    bits). Deferred carriers override the mask: an in-flight stale
    upload finishes regardless of membership (defer, not drop).

    ``m_ud_bits``: optional per-round upload-size override, ``(n_rounds,)``
    scalars or ``(n_rounds, n_clients)`` — the co-simulation feeds the
    measured (compressed) update size of each round.

    ``deadline_s``: optional round deadline(s), scalar or ``(n_rounds,)``
    — the upload phase is cut at the deadline and unfinished clients
    are handled per ``deadline_policy``.

    ``deadline_policy``: what happens to a straggler's unserved bits at
    the deadline — ``"defer"`` (carry to the next round, the default),
    ``"drop"`` (discard) or ``"partial"`` (discard, but report the
    served fraction as a usable partial update).

    ``buffer_k``: async (FedBuff) mode — ignore ``deadline_s`` (must be
    None) and fire each round's aggregation as soon as ``buffer_k``
    pending uploads complete; stragglers defer with staleness.

    ``faults`` (``repro.faults.FaultSchedule``): deterministic client
    dropout, upstream ONU/link outage windows and payload loss drawn
    from counter-based streams. Failed uploads retransmit under
    ``retry`` (``repro.faults.RetryPolicy``; defaults to
    ``RetryPolicy()`` when faults can fail uploads): the client backs
    off for ``delay_rounds(attempt)`` rounds — during which it is *not*
    re-admitted by the membership mask; retry suppression overrides the
    mask exactly like deferred carriers do, so a masked-in client can
    never hold two uploads in flight — then re-enters like a carrier
    (no fresh download, zero compute, full pending bits). Past
    ``max_retries`` it abandons the update and re-enters fresh through
    membership. A ``trivial`` fault schedule is bitwise identical to
    ``faults=None``.

    ``quorum_frac``: quorum aggregation — a deadlined round commits
    only when at least ``ceil(quorum_frac * n_pending)`` un-faulted
    uploads arrived by the deadline; otherwise the round's deadline
    doubles and the round re-runs (identical counter streams make the
    rerun a superset of the first pass), up to ``quorum_max_extends``
    times, after which the round reports ``quorum_met=False`` and the
    learning layer degrades to the previous global model. Requires
    ``deadline_s``; incompatible with ``buffer_k`` (async mode is its
    own arrival quorum).

    All array inputs are normalised and defensively copied once at
    construction: later mutation of the caller's arrays cannot desync
    the folded engine from the sequential/reference loops (which would
    otherwise re-read the caller's memory at different times).
    """

    n_rounds: int
    membership: Optional[np.ndarray] = None
    m_ud_bits: Optional[np.ndarray] = None
    deadline_s: Optional[object] = None
    deadline_policy: str = "defer"
    buffer_k: Optional[int] = None
    faults: Optional[FaultSchedule] = None
    retry: Optional[RetryPolicy] = None
    quorum_frac: Optional[float] = None
    quorum_max_extends: int = 2

    def __post_init__(self):
        if self.n_rounds < 1:
            raise ValueError("n_rounds must be >= 1")
        if self.deadline_policy not in DEADLINE_POLICIES:
            raise ValueError(
                f"unknown deadline_policy {self.deadline_policy!r}; "
                f"have {DEADLINE_POLICIES}"
            )
        if self.membership is not None:
            m = np.array(self.membership, dtype=bool)
            if m.ndim != 2 or m.shape[0] != self.n_rounds:
                raise ValueError(
                    f"membership must be (n_rounds, n_clients); "
                    f"got {m.shape}"
                )
            object.__setattr__(self, "membership", m)
        if self.deadline_s is not None:
            d = np.array(self.deadline_s, dtype=np.float64).reshape(-1)
            if d.size not in (1, self.n_rounds):
                raise ValueError(
                    f"deadline_s must be scalar or (n_rounds,); "
                    f"got {d.size} values for {self.n_rounds} rounds"
                )
            object.__setattr__(self, "deadline_s", d)
        elif self.deadline_policy != "defer":
            raise ValueError(
                f"deadline_policy={self.deadline_policy!r} needs "
                "deadline_s (without a deadline nothing is ever cut)"
            )
        if self.m_ud_bits is not None:
            m = np.array(self.m_ud_bits, dtype=np.float64)
            if m.shape[0] != self.n_rounds:
                raise ValueError(
                    f"m_ud_bits must lead with n_rounds="
                    f"{self.n_rounds}; got shape {m.shape}"
                )
            object.__setattr__(self, "m_ud_bits", m)
        if self.buffer_k is not None:
            if int(self.buffer_k) < 1:
                raise ValueError("buffer_k must be >= 1")
            if self.deadline_s is not None:
                raise ValueError(
                    "async mode (buffer_k) fires at the k-th arrival; "
                    "it cannot be combined with deadline_s"
                )
            object.__setattr__(self, "buffer_k", int(self.buffer_k))
        if self.faults is not None and not isinstance(
            self.faults, FaultSchedule
        ):
            raise TypeError("faults must be a repro.faults.FaultSchedule")
        if self.retry is not None and not isinstance(
            self.retry, RetryPolicy
        ):
            raise TypeError("retry must be a repro.faults.RetryPolicy")
        if self.quorum_frac is not None:
            q = float(self.quorum_frac)
            if not 0.0 < q <= 1.0:
                raise ValueError(
                    f"quorum_frac must be in (0, 1]; got {q}"
                )
            if self.buffer_k is not None:
                raise ValueError(
                    "async mode (buffer_k) is its own arrival quorum; "
                    "it cannot be combined with quorum_frac"
                )
            if self.deadline_s is None:
                raise ValueError(
                    "quorum_frac needs deadline_s: without a deadline "
                    "every pending upload always arrives"
                )
            object.__setattr__(self, "quorum_frac", q)
        if int(self.quorum_max_extends) < 0:
            raise ValueError("quorum_max_extends must be >= 0")
        object.__setattr__(
            self, "quorum_max_extends", int(self.quorum_max_extends)
        )

    @property
    def asynchronous(self) -> bool:
        return self.buffer_k is not None

    @property
    def active_faults(self) -> Optional[FaultSchedule]:
        """The fault schedule, or None when absent/trivial — every
        fault code path gates on this, which is what makes a trivial
        ``FaultSchedule()`` bitwise identical to ``faults=None``."""
        f = self.faults
        return None if f is None or f.trivial else f

    @property
    def retry_policy(self) -> RetryPolicy:
        return self.retry if self.retry is not None else RetryPolicy()

    @property
    def couples_rounds(self) -> bool:
        """True when state crosses round boundaries (no folding)."""
        faults = self.active_faults
        return (
            self.asynchronous
            or (self.deadline_s is not None
                and self.deadline_policy == "defer")
            or (faults is not None and faults.couples_rounds)
            or self.quorum_frac is not None
        )

    def deadline(self, r: int) -> Optional[float]:
        if self.deadline_s is None:
            return None
        d = self.deadline_s
        return float(d[r] if d.size > 1 else d[0])

    def round_m_ud(self, r: int, j: int, default: float) -> float:
        if self.m_ud_bits is None:
            return default
        m = self.m_ud_bits
        return float(m[r] if m.ndim == 1 else m[r, j])


@dataclass
class TimelineRound:
    """One round of one case's timeline."""

    round_index: int
    sync_time: float
    t_start: float
    t_end: float
    ul_bits: Dict[int, float]       # bits actually served this round
    arrived: List[int]              # clients whose update completed
    deferred: Dict[int, float]      # bits carried into the next round
    result: Optional[RoundResult]   # None for empty (no-client) rounds
    # rounds elapsed since each arrived client downloaded its model
    # (0 unless the client deferred across rounds — defer/async modes)
    staleness: Dict[int, int] = field(default_factory=dict)
    # deadline_policy="drop": bits discarded at the deadline per client
    dropped: Dict[int, float] = field(default_factory=dict)
    # deadline_policy="partial": served fraction (usable partial update)
    # per client cut at the deadline
    partial: Dict[int, float] = field(default_factory=dict)
    # fault outcomes (repro.faults): clients that died mid-upload this
    # round (bits they served before dying — wasted wire time), and
    # completed uploads whose payload arrived corrupted
    failed: Dict[int, float] = field(default_factory=dict)
    lost: List[int] = field(default_factory=list)
    # failed clients' scheduled retransmission round / abandonments
    retry_at: Dict[int, int] = field(default_factory=dict)
    gave_up: List[int] = field(default_factory=list)
    # quorum aggregation: whether the round met its arrival quorum
    # (None = no quorum configured) and how often the deadline doubled
    quorum_met: Optional[bool] = None
    deadline_extensions: int = 0
    # multi-tenant cases: job_id -> this round's per-job sync time
    # (CPS tier; empty for single-tenant rounds and rounds the job
    # sat out under its cadence)
    job_sync: Dict[int, float] = field(default_factory=dict)


@dataclass
class TimelineResult:
    policy: str
    load: float
    seed: int
    rounds: List[TimelineRound]

    @property
    def sync_times(self) -> np.ndarray:
        return np.array([r.sync_time for r in self.rounds])

    @property
    def total_time_s(self) -> float:
        return float(self.sync_times.sum())


# ---------------------------------------------------------------------------
# per-round workload construction (shared by engine and reference paths)
# ---------------------------------------------------------------------------


class _RetryEntry(NamedTuple):
    """An in-flight retransmission: due round + the bits to re-send."""

    due_round: int
    bits: float
    attempt: int


class _FaultState:
    """Per-case fault bookkeeping carried across rounds by every
    driver (sequential, async and the reference loop)."""

    __slots__ = ("retries", "attempts")

    def __init__(self):
        # in-flight retransmissions: client -> _RetryEntry
        self.retries: Dict[int, _RetryEntry] = {}
        # consecutive failed attempts per client (cleared on a clean
        # arrival or on giving up)
        self.attempts: Dict[int, int] = {}


_MIN_FAULT_BITS = 2.0   # dropout truncation floor (avoid 0-bit uploads)


def _round_setup(case: SweepCase, schedule: TimelineSchedule, r: int,
                 carry: Dict[int, float],
                 retries: Optional[Dict[int, _RetryEntry]] = None):
    """(clients_r, no_dl_ids, rem_start, drops) for round ``r``.

    Fresh members take the round's upload size; carriers (clients with
    deferred bits) re-enter with their remaining bits, zero compute time
    and no model download, regardless of the membership mask. Retry
    entries behave the same two ways: one *due* (``due_round <= r``)
    re-enters exactly like a carrier; one still backing off suppresses
    the client's fresh membership entry — the invariant that a
    membership mask can never revive a client inside an in-flight
    deferred/retry upload holds by construction (regression-tested).

    ``drops`` maps this round's dropout victims (``schedule.faults``)
    to their *full* pending bits; their simulated upload is truncated
    at the death point (``_MIN_FAULT_BITS`` floor), and the full
    payload is what the retry will re-send.
    """
    clients = case.workload.clients
    mask = (schedule.membership[r] if schedule.membership is not None
            else np.ones(len(clients), bool))
    retries = retries or {}
    out = []
    rem_start: Dict[int, float] = {}
    no_dl = set(carry)
    for j, c in enumerate(clients):
        cid = c.client_id
        if cid in carry:
            if cid in retries:       # pragma: no cover - internal guard
                raise RuntimeError(
                    f"client {cid} is both a deferred carrier and an "
                    "in-flight retry at round "
                    f"{r}: fault bookkeeping desynced"
                )
            bits = carry[cid]
            out.append(replace(c, t_ud=0.0, t_dl=0.0, m_ud_bits=bits))
            rem_start[cid] = bits
        elif cid in retries:
            ent = retries[cid]
            if ent.due_round > r:
                continue             # backing off: mask never revives
            retries.pop(cid)         # in flight again from this round
            out.append(replace(c, t_ud=0.0, t_dl=0.0,
                               m_ud_bits=ent.bits))
            rem_start[cid] = ent.bits
            no_dl.add(cid)
        elif mask[j]:
            bits = schedule.round_m_ud(r, j, c.m_ud_bits)
            out.append(replace(c, m_ud_bits=bits))
            rem_start[cid] = bits
    drops: Dict[int, float] = {}
    faults = schedule.active_faults
    if faults is not None and faults.dropout_rate > 0.0 and rem_start:
        frac = faults.dropouts(r, sorted(rem_start), case.seed)
        if frac:
            for i, c in enumerate(out):
                f = frac.get(c.client_id)
                if f is None:
                    continue
                full = c.m_ud_bits
                cut = min(max(f * full, _MIN_FAULT_BITS), full)
                out[i] = replace(c, m_ud_bits=cut)
                rem_start[c.client_id] = cut
                drops[c.client_id] = full
    return out, frozenset(no_dl), rem_start, drops


def _round_view(r: int, t_start: float, result: Optional[RoundResult],
                rem_start: Dict[int, float], t_aggregate: float,
                policy: str = "defer",
                entry: Optional[Dict[int, int]] = None,
                ) -> Tuple[TimelineRound, Dict[int, float]]:
    """Fold one round's RoundResult into a TimelineRound + next carry.

    ``entry`` maps each pending client to the round it downloaded its
    model (maintained by the drivers); arrived clients report staleness
    ``r - entry``.  A ``None`` result is only legal for a round with no
    pending clients — carriers must always be routed into a non-empty
    round, or their bits would silently vanish.
    """
    if result is None:
        if rem_start:
            raise RuntimeError(
                f"round {r} produced no simulation result but has "
                f"pending clients {sorted(rem_start)}: carriers must be "
                "routed into a non-empty round, not dropped"
            )
        rnd = TimelineRound(
            round_index=r, sync_time=t_aggregate, t_start=t_start,
            t_end=t_start + t_aggregate, ul_bits={}, arrived=[],
            deferred={}, result=None,
        )
        return rnd, {}
    remaining = dict(result.ul_remaining or {})
    ul_bits = {
        cid: rem_start[cid] - remaining.get(cid, 0.0)
        for cid in rem_start
    }
    arrived = sorted(cid for cid in rem_start if cid not in remaining)
    staleness = {
        cid: (r - entry.get(cid, r)) if entry is not None else 0
        for cid in arrived
    }
    deferred: Dict[int, float] = {}
    dropped: Dict[int, float] = {}
    partial: Dict[int, float] = {}
    if policy == "defer":
        deferred = remaining
    elif policy == "drop":
        dropped = remaining
    elif policy == "partial":
        partial = {cid: ul_bits[cid] / rem_start[cid] for cid in remaining}
    else:  # pragma: no cover - schedule validation rejects earlier
        raise ValueError(f"unknown deadline_policy {policy!r}")
    rnd = TimelineRound(
        round_index=r, sync_time=result.sync_time, t_start=t_start,
        t_end=t_start + result.sync_time, ul_bits=ul_bits,
        arrived=arrived, deferred=deferred, result=result,
        staleness=staleness, dropped=dropped, partial=partial,
    )
    return rnd, deferred


def _observe_round(collector, case, rnd: TimelineRound,
                   deadline: Optional[float]) -> None:
    """Fold one TimelineRound into the collector: round wall time and
    outcome counts (``record_round``), staleness of arrived updates,
    and deadline slack (deadline − completion) of clients that made the
    cut. Pure reads — a ``None`` collector is a no-op and simulation
    state is never touched."""
    if collector is None:
        return
    if rnd.staleness:
        collector.record_staleness(list(rnd.staleness.values()))
    if deadline is not None and rnd.result is not None and rnd.arrived:
        slack = [deadline - rnd.result.ul_done.get(cid, np.nan)
                 for cid in rnd.arrived]
        collector.record_slack(case.policy, case.load, slack)
    collector.record_round(
        policy=case.policy, load=case.load, seed=case.seed,
        round=rnd.round_index, sync_time=rnd.sync_time,
        t_start=rnd.t_start, t_end=rnd.t_end,
        ul_bits=float(sum(rnd.ul_bits.values())),
        n_arrived=len(rnd.arrived), n_deferred=len(rnd.deferred),
        n_dropped=len(rnd.dropped), n_partial=len(rnd.partial),
    )


def _round_faulted(schedule: TimelineSchedule, case, r: int,
                   rem_start: Dict[int, float],
                   drops: Dict[int, float]) -> frozenset:
    """The round's faulted clients: dropout victims plus the loss draw.

    The loss draw covers every *pending* client (not just the arrived
    ones), so the set is a pure function of ``(round, pending)`` —
    identical for the quorum rerun, the async probe pass and the
    reference oracle.
    """
    faults = schedule.active_faults
    lost = (faults.losses(r, sorted(rem_start), case.seed)
            if faults is not None and faults.loss_rate > 0.0 and rem_start
            else frozenset())
    return frozenset(drops) | lost


def _effective_arrived(result: RoundResult, rem_start: Dict[int, float],
                       faulted: frozenset) -> List[int]:
    """Uploads that completed AND were not cancelled by a fault — the
    arrivals the quorum counts (shared by engine drivers and oracle)."""
    remaining = result.ul_remaining or {}
    return [cid for cid in rem_start
            if cid not in remaining and cid not in faulted]


def _apply_round_faults(schedule: TimelineSchedule, case, r: int,
                        rnd: TimelineRound, rem_start: Dict[int, float],
                        carry: Dict[int, float], drops: Dict[int, float],
                        fstate: _FaultState,
                        collector=None) -> Dict[int, float]:
    """Cancel faulted arrivals, book retry-with-backoff entries and
    return the updated carry (shared by the sequential/async drivers
    and the reference loop — both backends fold faults identically).

    Dropout victims are failed this round regardless of deadline
    policy: their served bits were wasted wire time (``rnd.failed``),
    and the retry re-sends the *full* payload. Loss victims completed
    the wire transfer but the payload is discarded (``rnd.lost``); the
    retry re-sends the failure round's pending bits (fragment
    retransmission is not modelled). Either way the client backs off
    ``retry.delay_rounds(attempt)`` rounds (``rnd.retry_at``) or — past
    ``max_retries`` attempts — abandons the update (``rnd.gave_up``)
    and re-enters fresh through membership.
    """
    faults = schedule.active_faults
    if faults is None:
        return carry
    retry = schedule.retry_policy

    def book(cid: int, bits: float):
        attempt = fstate.attempts.get(cid, 0) + 1
        if attempt > retry.max_retries:
            fstate.attempts.pop(cid, None)
            rnd.gave_up.append(cid)
            if collector is not None:
                collector.event("fault.gave_up", round=r, client=cid,
                                attempts=attempt - 1, seed=case.seed)
            return
        fstate.attempts[cid] = attempt
        due = r + retry.delay_rounds(attempt)
        fstate.retries[cid] = _RetryEntry(due, bits, attempt)
        rnd.retry_at[cid] = due

    for cid in sorted(drops):
        rnd.failed[cid] = rnd.ul_bits.get(cid, 0.0)
        if cid in rnd.arrived:
            rnd.arrived.remove(cid)
        rnd.staleness.pop(cid, None)
        carry.pop(cid, None)
        rnd.deferred.pop(cid, None)
        rnd.dropped.pop(cid, None)
        rnd.partial.pop(cid, None)
        book(cid, drops[cid])
        if collector is not None:
            collector.event("fault.dropout", round=r, client=cid,
                            wasted_bits=rnd.failed[cid], seed=case.seed)
    if faults.loss_rate > 0.0 and rnd.arrived:
        lost_draw = faults.losses(r, sorted(rem_start), case.seed)
        for cid in [c for c in rnd.arrived if c in lost_draw]:
            rnd.arrived.remove(cid)
            rnd.staleness.pop(cid, None)
            rnd.lost.append(cid)
            book(cid, rem_start[cid])
            if collector is not None:
                collector.event("fault.loss", round=r, client=cid,
                                bits=rem_start[cid], seed=case.seed)
    for cid in rnd.arrived:          # a clean arrival resets backoff
        fstate.attempts.pop(cid, None)
    return carry


def _kth_completion(result: RoundResult, rem_start: Dict[int, float],
                    buffer_k: int,
                    exclude: frozenset = frozenset()) -> Optional[float]:
    """The async cutoff: completion time of the k-th pending upload.

    Zero-bit uploads complete at the round start (their ``ul_done`` is
    NaN — nothing was ever queued). Fewer than k pending clients fall
    back to the last completion (a plain full round). ``exclude``
    (the round's faulted clients) never counts toward the buffer — the
    aggregator waits for the k-th *valid* update; if nothing valid is
    pending the round runs free (``None``: no deadline)."""
    times = sorted(
        0.0 if np.isnan(result.ul_done[cid]) else float(result.ul_done[cid])
        for cid in rem_start if cid not in exclude
    )
    if not times:
        return None
    return times[min(buffer_k, len(times)) - 1]


def _validate(cases: Sequence[SweepCase], schedule: TimelineSchedule):
    cases = list(cases)
    if not cases:
        raise ValueError("timeline sweep needs at least one case")
    for case in cases:
        if case.dl_arrivals is not None or case.ul_arrivals is not None:
            raise ValueError(
                "timeline cases draw from counter streams; injected "
                "arrival matrices are a single-round parity hook"
            )
        if schedule.membership is not None and (
            schedule.membership.shape[1] != len(case.workload.clients)
        ):
            raise ValueError(
                "membership mask width must match workload.clients"
            )
    return cases


# ---------------------------------------------------------------------------
# engine-backed drivers
# ---------------------------------------------------------------------------


def _case_n_pons(case) -> int:
    return case.topology.n_pons if case.topology is not None else 1


def _build_rows(cases, schedule, r, carries, fstates=None):
    """Per-round SweepCase rows + alignment metadata for a batch.

    Metadata rows are ``(b, row_index_or_None, rem_start, drops)``;
    ``fstates`` (per-case ``_FaultState``) supplies in-flight retries
    whose due entries re-enter this round.
    """
    row_cases = []
    row_meta = []
    for b, case in enumerate(cases):
        clients_r, no_dl, rem_start, drops = _round_setup(
            case, schedule, r, carries[b],
            fstates[b].retries if fstates is not None else None,
        )
        if not clients_r:
            row_meta.append((b, None, rem_start, drops))
            continue
        wl = FLRoundWorkload(
            clients=clients_r,
            model_bits=case.workload.model_bits,
            t_aggregate=case.workload.t_aggregate,
        )
        row_meta.append((b, len(row_cases), rem_start, drops))
        row_cases.append(SweepCase(
            workload=wl, load=case.load, policy=case.policy,
            seed=case.seed, stream_round=r, no_dl_ids=no_dl,
            topology=case.topology,
        ))
    return row_cases, row_meta


def _round_outages(cases, schedule, r, row_meta):
    """Per-engine-row outage windows for round ``r`` (aligned with the
    round's row_cases), or None when outage injection is inactive."""
    faults = schedule.active_faults
    if faults is None or faults.outage_rate <= 0.0:
        return None
    n_rows = sum(1 for _, ridx, _, _ in row_meta if ridx is not None)
    outages: List[Optional[np.ndarray]] = [None] * n_rows
    for b, ridx, _, _ in row_meta:
        if ridx is not None:
            outages[ridx] = faults.outage_windows(
                r, _case_n_pons(cases[b]), cases[b].seed
            )
    return outages


def _advance_rounds(cfg, cases, schedule, t_round_hint, max_t, policy,
                    deadline_fn, collector=None, backend=None):
    """The shared round-by-round driver: build rows, resolve each
    round's deadline(s) via ``deadline_fn(r, row_cases, row_meta,
    outages)`` (a scalar, or a per-row list), advance the engine, apply
    the round's faults/quorum and carry deferred + retry state forward.

    Quorum reruns (doubled deadline) re-advance only the unmet rows;
    like the async probe pass they stay uninstrumented at the engine
    level — only the first pass feeds phase metrics — but each
    extension emits a ``quorum.extend`` event.
    """
    import math

    from repro.obs.trace import maybe_span

    B = len(cases)
    carries: List[Dict[int, float]] = [{} for _ in range(B)]
    entries: List[Dict[int, int]] = [{} for _ in range(B)]
    fstates = [_FaultState() for _ in range(B)]
    t_now = np.zeros(B)
    out = [TimelineResult(policy=c.policy, load=c.load, seed=c.seed,
                          rounds=[]) for c in cases]
    quorum = schedule.quorum_frac
    for r in range(schedule.n_rounds):
        with maybe_span(collector, "timeline.setup", round=r):
            row_cases, row_meta = _build_rows(
                cases, schedule, r, carries, fstates
            )
        for b, _, rem_start, _ in row_meta:
            for cid in rem_start:
                entries[b].setdefault(cid, r)
        outages = _round_outages(cases, schedule, r, row_meta)
        deadlines = deadline_fn(r, row_cases, row_meta, outages)
        with maybe_span(collector, "timeline.round", round=r,
                        rows=len(row_cases)):
            results = _round_sweep(
                cfg, row_cases, t_round_hint=t_round_hint, max_t=max_t,
                ul_deadline_s=deadlines, ul_outage_s=outages,
                collector=collector, backend=backend,
            ) if row_cases else []
        ext_counts: Dict[int, int] = {}
        met: Dict[int, bool] = {}
        if quorum is not None and row_cases:
            dls = (list(deadlines)
                   if isinstance(deadlines, (list, tuple, np.ndarray))
                   else [deadlines] * len(row_cases))

            def _unmet():
                redo = []
                for b, ridx, rem_start, drops in row_meta:
                    if ridx is None or dls[ridx] is None:
                        continue
                    faulted = _round_faulted(
                        schedule, cases[b], r, rem_start, drops
                    )
                    got = len(_effective_arrived(
                        results[ridx], rem_start, faulted
                    ))
                    need = max(1, math.ceil(quorum * len(rem_start)))
                    met[ridx] = got >= need
                    if got < need:
                        redo.append((b, ridx))
                return redo

            for _ in range(schedule.quorum_max_extends):
                redo = _unmet()
                if not redo:
                    break
                for b, ridx in redo:
                    dls[ridx] = float(dls[ridx]) * 2.0
                    ext_counts[ridx] = ext_counts.get(ridx, 0) + 1
                    if collector is not None:
                        collector.event(
                            "quorum.extend", round=r,
                            seed=cases[b].seed,
                            deadline_s=dls[ridx],
                            extension=ext_counts[ridx],
                        )
                sub_idx = [ridx for _, ridx in redo]
                sub = _round_sweep(
                    cfg, [row_cases[i] for i in sub_idx],
                    t_round_hint=t_round_hint, max_t=max_t,
                    ul_deadline_s=[dls[i] for i in sub_idx],
                    ul_outage_s=(None if outages is None else
                                 [outages[i] for i in sub_idx]),
                    backend=backend,
                )
                for j, ridx in enumerate(sub_idx):
                    results[ridx] = sub[j]
            else:
                _unmet()        # final verdicts after the last extend
            deadlines = dls
        per_row_dl = isinstance(deadlines, (list, tuple, np.ndarray))
        with maybe_span(collector, "timeline.views", round=r):
            for b, ridx, rem_start, drops in row_meta:
                res = results[ridx] if ridx is not None else None
                rnd, carry = _round_view(
                    r, float(t_now[b]), res, rem_start,
                    cases[b].workload.t_aggregate, policy, entries[b],
                )
                if ridx is not None and ridx in met:
                    rnd.quorum_met = met[ridx]
                    rnd.deadline_extensions = ext_counts.get(ridx, 0)
                carry = _apply_round_faults(
                    schedule, cases[b], r, rnd, rem_start, carry, drops,
                    fstates[b], collector,
                )
                out[b].rounds.append(rnd)
                carries[b] = carry
                entries[b] = {
                    cid: ent for cid, ent in entries[b].items()
                    if cid in carry or cid in fstates[b].retries
                }
                t_now[b] += rnd.sync_time
                if collector is not None:
                    dl = (deadlines[ridx]
                          if per_row_dl and ridx is not None else
                          None if per_row_dl else deadlines)
                    _observe_round(collector, cases[b], rnd, dl)
    return out


def _sequential(cfg, cases, schedule, t_round_hint, max_t,
                collector=None, backend=None):
    """Round-by-round engine advance, carrying deferred bits (the only
    legal order under defer deadlines; also the PR 2 per-round loop that
    the folded mode is benchmarked against)."""
    return _advance_rounds(
        cfg, cases, schedule, t_round_hint, max_t,
        schedule.deadline_policy,
        lambda r, row_cases, row_meta, outages: schedule.deadline(r),
        collector=collector, backend=backend,
    )


def _async(cfg, cases, schedule, t_round_hint, max_t, collector=None,
           backend=None):
    """FedBuff-style async rounds: each round is cut at the completion
    time of the ``buffer_k``-th pending upload (two engine passes — a
    free-running pass locates ``t_k``, a deadline pass at ``t_k``
    yields the stragglers' exact unserved bits), and stragglers defer
    with staleness. Cycles whose start precedes ``t_k`` complete, so
    the round's served bits reflect the cutoff at cycle granularity —
    the same rule the reference oracle applies.
    """
    k = schedule.buffer_k

    def deadline_fn(r, row_cases, row_meta, outages):
        # NOTE: the free-running probe pass stays uninstrumented — only
        # the deadline pass (the round that actually happens) feeds the
        # collector, so nothing is double-counted.
        free = _round_sweep(
            cfg, row_cases, t_round_hint=t_round_hint, max_t=max_t,
            ul_outage_s=outages, backend=backend,
        )
        deadlines: List[Optional[float]] = [None] * len(row_cases)
        for b, ridx, rem_start, drops in row_meta:
            if ridx is not None:
                deadlines[ridx] = _kth_completion(
                    free[ridx], rem_start, k,
                    _round_faulted(schedule, cases[b], r, rem_start,
                                   drops),
                )
        return deadlines

    return _advance_rounds(
        cfg, cases, schedule, t_round_hint, max_t, "defer", deadline_fn,
        collector=collector, backend=backend,
    )


def _folded(cfg, cases, schedule, t_round_hint, max_t, collector=None,
            backend=None):
    """The whole timeline as ONE stacked simulation: the round axis is
    folded into the engine batch axis (legal whenever rounds are
    independent given their start times — no deadline, or drop/partial
    policies whose stragglers never carry state forward; each row then
    runs under its own round's deadline)."""
    faults = schedule.active_faults
    has_outage = faults is not None and faults.outage_rate > 0.0
    rows = []
    row_deadlines: List[Optional[float]] = []
    row_outages: List[Optional[np.ndarray]] = []
    meta = []            # (b, r, rem_start, row_index or None)
    from repro.obs.trace import maybe_span

    with maybe_span(collector, "timeline.setup", cases=len(cases),
                    rounds=schedule.n_rounds):
        for b, case in enumerate(cases):
            for r in range(schedule.n_rounds):
                clients_r, _, rem_start, _ = _round_setup(
                    case, schedule, r, {}
                )
                if not clients_r:
                    meta.append((b, r, rem_start, None))
                    continue
                wl = FLRoundWorkload(
                    clients=clients_r,
                    model_bits=case.workload.model_bits,
                    t_aggregate=case.workload.t_aggregate,
                )
                meta.append((b, r, rem_start, len(rows)))
                rows.append(SweepCase(
                    workload=wl, load=case.load, policy=case.policy,
                    seed=case.seed, stream_round=r,
                    topology=case.topology,
                ))
                row_deadlines.append(schedule.deadline(r))
                if has_outage:
                    # outage injection never couples rounds (dark cycles
                    # just delay the round's own uploads), so it folds as
                    # one more per-row axis: each row carries its round's
                    # counter-keyed window
                    row_outages.append(faults.outage_windows(
                        r, _case_n_pons(case), case.seed
                    ))

    has_deadline = schedule.deadline_s is not None
    with maybe_span(collector, "timeline.folded", rows=len(rows),
                    rounds=schedule.n_rounds):
        results = _round_sweep(
            cfg, rows, t_round_hint=t_round_hint, max_t=max_t,
            ul_deadline_s=row_deadlines if has_deadline else None,
            ul_outage_s=row_outages if has_outage else None,
            collector=collector, backend=backend,
        ) if rows else []
    out = [TimelineResult(policy=c.policy, load=c.load, seed=c.seed,
                          rounds=[]) for c in cases]
    t_now = np.zeros(len(cases))
    with maybe_span(collector, "timeline.views", rows=len(meta)):
        for b, r, rem_start, ridx in meta:
            res = results[ridx] if ridx is not None else None
            rnd, _ = _round_view(
                r, float(t_now[b]), res, rem_start,
                cases[b].workload.t_aggregate, schedule.deadline_policy,
            )
            out[b].rounds.append(rnd)
            t_now[b] += rnd.sync_time
            if collector is not None:
                _observe_round(collector, cases[b], rnd,
                               schedule.deadline(r))
    return out


def _jobs_schedule_check(schedule: TimelineSchedule) -> None:
    """Multi-job timelines fold rounds by construction — reject every
    schedule feature that couples rounds or rewrites per-round
    workloads (those are single-tenant features; per-job round cadence
    is expressed through ``JobSpec.period``/``phase`` instead)."""
    if (schedule.membership is not None
            or schedule.m_ud_bits is not None
            or schedule.deadline_s is not None
            or schedule.buffer_k is not None
            or schedule.active_faults is not None
            or schedule.quorum_frac is not None):
        raise ValueError(
            "multi-job timelines need a plain schedule (n_rounds "
            "only): membership masks, per-round update sizes, "
            "deadlines, async buffering, fault injection and quorum "
            "extension are single-job features — encode per-job "
            "cadence via JobSpec.period/phase instead"
        )


def _folded_jobs(cfg, cases, schedule, mode, t_round_hint, max_t,
                 collector=None, backend=None):
    """Folded driver for multi-tenant cases: each round keeps only the
    jobs active under their cadence (``JobSpec.active_in``), the round
    axis folds into the engine batch exactly like ``_folded``, and the
    per-job CPS sync times land in ``TimelineRound.job_sync``."""
    if not all(case.jobs is not None for case in cases):
        raise ValueError(
            "a timeline sweep cannot mix multi-job and single-job "
            "cases; split them into separate sweeps"
        )
    _jobs_schedule_check(schedule)
    if mode not in ("auto", "folded"):
        raise ValueError(
            "multi-job timelines have independent rounds and always "
            f"fold; mode {mode!r} is unavailable"
        )
    rows = []
    meta = []            # (b, r, rem_start, row_index or None)
    for b, case in enumerate(cases):
        for r in range(schedule.n_rounds):
            active = tuple(j for j in case.jobs if j.active_in(r))
            keep = {cid for j in active for cid in j.clients}
            clients_r = [c for c in case.workload.clients
                         if c.client_id in keep]
            rem_start = {c.client_id: c.m_ud_bits for c in clients_r}
            if not clients_r:
                meta.append((b, r, rem_start, None))
                continue
            wl = FLRoundWorkload(
                clients=clients_r,
                model_bits=case.workload.model_bits,
                t_aggregate=case.workload.t_aggregate,
            )
            meta.append((b, r, rem_start, len(rows)))
            rows.append(replace(case, workload=wl, stream_round=r,
                                jobs=active))
    from repro.obs.trace import maybe_span

    with maybe_span(collector, "timeline.folded_jobs", rows=len(rows),
                    rounds=schedule.n_rounds):
        results = _round_sweep(
            cfg, rows, t_round_hint=t_round_hint, max_t=max_t,
            collector=collector, backend=backend,
        ) if rows else []
    out = [TimelineResult(policy=c.policy, load=c.load, seed=c.seed,
                          rounds=[]) for c in cases]
    t_now = np.zeros(len(cases))
    for b, r, rem_start, ridx in meta:
        res = results[ridx] if ridx is not None else None
        rnd, _ = _round_view(
            r, float(t_now[b]), res, rem_start,
            cases[b].workload.t_aggregate, "defer",
        )
        if res is not None and res.job_stats:
            rnd.job_sync = {jid: js.sync_time
                            for jid, js in res.job_stats.items()}
        out[b].rounds.append(rnd)
        t_now[b] += rnd.sync_time
        if collector is not None:
            _observe_round(collector, cases[b], rnd, None)
    return out


def _timeline_sweep(cfg, cases: Sequence[SweepCase],
                    schedule: TimelineSchedule,
                    mode: str = "auto",
                    t_round_hint: float = 10.0,
                    max_t: float = 600.0,
                    collector=None,
                    backend: Optional[str] = None,
                    ) -> List[TimelineResult]:
    """Advance the full multi-round timeline for every case.

    ``mode="auto"`` folds the round axis into the batch (one stacked
    simulation) when nothing couples consecutive rounds — no deadline,
    or ``deadline_policy`` in ``{"drop", "partial"}`` — and falls back
    to the sequential carry loop for defer deadlines;
    ``schedule.buffer_k`` selects the async (FedBuff) driver.
    ``"folded"``/``"sequential"`` force a path (parity tests check they
    agree when both are legal). Multi-job cases (``SweepCase.jobs``)
    always fold — their rounds are independent by construction — and
    report per-job sync times via ``TimelineRound.job_sync``.

    ``collector`` (``repro.obs.Collector``, optional) records engine
    phase metrics, per-round outcomes (``record_round``), upload-delay
    and deadline-slack histograms and staleness counts; ``None`` (the
    default) is bitwise identical to an uninstrumented run. Async
    schedules instrument only the deadline pass — the free-running
    probe pass is a search, not a simulated round.
    """
    cases = _validate(cases, schedule)
    if any(case.jobs is not None for case in cases):
        return _folded_jobs(cfg, cases, schedule, mode, t_round_hint,
                            max_t, collector=collector, backend=backend)
    if schedule.asynchronous:
        if mode == "folded":
            raise ValueError(
                "async rounds couple consecutive rounds (stragglers "
                "defer); folded mode is unavailable"
            )
        return _async(cfg, cases, schedule, t_round_hint, max_t,
                      collector=collector, backend=backend)
    if mode == "auto":
        mode = "sequential" if schedule.couples_rounds else "folded"
    if mode == "folded":
        if schedule.couples_rounds:
            raise ValueError(
                "schedule couples consecutive rounds (deadline "
                "deferral, dropout/loss retries or quorum extension); "
                "folded mode requires independent rounds — no "
                "deadline or drop/partial policies, and at most "
                "outage-only fault injection"
            )
        return _folded(cfg, cases, schedule, t_round_hint, max_t,
                       collector=collector, backend=backend)
    if mode == "sequential":
        return _sequential(cfg, cases, schedule, t_round_hint, max_t,
                           collector=collector, backend=backend)
    raise ValueError(f"unknown mode {mode!r}")



# ---------------------------------------------------------------------------
# reference loop (parity oracle)
# ---------------------------------------------------------------------------


def simulate_timeline_reference(cfg, cases: Sequence[SweepCase],
                                schedule: TimelineSchedule,
                                t_round_hint: float = 10.0,
                                max_t: float = 600.0,
                                ) -> List[TimelineResult]:
    """Per-round loop over the cycle-by-cycle *dict* simulator.

    Every round rebuilds the reference simulator from scratch and feeds
    it the engine's counter-based arrival streams
    (``CounterStream.source``), so the timeline engine must reproduce
    its sync times and per-round bits exactly (rtol 1e-6) — including
    elastic membership, all three deadline policies and async rounds
    (the same two-pass k-th-completion rule, on fresh stream cursors
    per pass).
    """
    from repro.kernels.traffic.ops import make_stream_key
    from repro.net.engine import _case_bg_rate
    from repro.net.multi_pon import simulate_multi_pon_round
    from repro.net.sim import simulate_round_reference
    from repro.net.traffic import CounterStream

    import math

    cases = _validate(cases, schedule)
    policy = schedule.deadline_policy
    quorum = schedule.quorum_frac
    out = []
    for case in cases:
        carry: Dict[int, float] = {}
        entry: Dict[int, int] = {}
        fstate = _FaultState()
        t_now = 0.0
        res = TimelineResult(policy=case.policy, load=case.load,
                             seed=case.seed, rounds=[])
        for r in range(schedule.n_rounds):
            clients_r, no_dl, rem_start, drops = _round_setup(
                case, schedule, r, carry, fstate.retries
            )
            for cid in rem_start:
                entry.setdefault(cid, r)
            if not clients_r:
                rnd, carry = _round_view(
                    r, t_now, None, rem_start,
                    case.workload.t_aggregate, policy, entry,
                )
                res.rounds.append(rnd)
                t_now += rnd.sync_time
                continue
            wl = FLRoundWorkload(
                clients=clients_r,
                model_bits=case.workload.model_bits,
                t_aggregate=case.workload.t_aggregate,
            )
            faults = schedule.active_faults
            outage = (faults.outage_windows(r, _case_n_pons(case),
                                            case.seed)
                      if faults is not None and faults.outage_rate > 0.0
                      else None)

            def run_ref(deadline):
                """One reference round under ``deadline`` — fresh
                stream cursors per call, so the async two-pass replays
                the identical arrival process."""
                if case.topology is not None and not case.topology.trivial:
                    # the cycle-by-cycle multi-PON oracle keys its own
                    # (seed, phase, round, pon) counter streams
                    return simulate_multi_pon_round(
                        cfg, case.topology, wl, case.load, case.policy,
                        seed=case.seed, t_round_hint=t_round_hint,
                        max_t=max_t, ul_deadline_s=deadline,
                        no_dl_ids=no_dl, stream_round=r,
                        ul_outage_s=outage,
                    )
                row = SweepCase(workload=wl, load=case.load,
                                policy=case.policy, seed=case.seed)
                per_onu = (_case_bg_rate(row, cfg, t_round_hint)
                           / cfg.n_onus)
                streams = [
                    CounterStream(
                        make_stream_key(case.seed, phase, r), per_onu,
                        cfg.cycle_time_s, cfg.n_onus,
                        burst_packets=cfg.bg_burst_packets,
                    )
                    for phase in (0, 1)
                ]
                return simulate_round_reference(
                    cfg, wl, case.load, case.policy, seed=case.seed,
                    t_round_hint=t_round_hint,
                    _dl_sources=[streams[0].source(i)
                                 for i in range(cfg.n_onus)],
                    _ul_sources=[streams[1].source(i)
                                 for i in range(cfg.n_onus)],
                    ul_deadline_s=deadline,
                    no_dl_ids=no_dl,
                    ul_outage_s=(None if outage is None else
                                 (float(outage[0, 0]),
                                  float(outage[0, 1]))),
                )

            quorum_met: Optional[bool] = None
            extensions = 0
            if schedule.asynchronous:
                free = run_ref(None)
                faulted = _round_faulted(schedule, case, r, rem_start,
                                         drops)
                result = run_ref(
                    _kth_completion(free, rem_start, schedule.buffer_k,
                                    faulted)
                )
            elif quorum is not None:
                # same extend-until-met loop as the engine driver:
                # identical counter streams make each rerun a superset
                # of the previous pass
                faulted = _round_faulted(schedule, case, r, rem_start,
                                         drops)
                need = max(1, math.ceil(quorum * len(rem_start)))
                dl = schedule.deadline(r)
                result = run_ref(dl)
                while True:
                    got = len(_effective_arrived(result, rem_start,
                                                 faulted))
                    quorum_met = got >= need
                    if (quorum_met
                            or extensions >= schedule.quorum_max_extends):
                        break
                    dl = float(dl) * 2.0
                    extensions += 1
                    result = run_ref(dl)
            else:
                result = run_ref(schedule.deadline(r))
            rnd, carry = _round_view(
                r, t_now, result, rem_start,
                case.workload.t_aggregate, policy, entry,
            )
            rnd.quorum_met = quorum_met
            rnd.deadline_extensions = extensions
            carry = _apply_round_faults(
                schedule, case, r, rnd, rem_start, carry, drops, fstate,
            )
            entry = {cid: ent for cid, ent in entry.items()
                     if cid in carry or cid in fstate.retries}
            res.rounds.append(rnd)
            t_now += rnd.sync_time
        out.append(res)
    return out
