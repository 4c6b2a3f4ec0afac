"""FL × PON co-simulation: real JAX training + network timing per round.

Couples the ``CPSServer`` (actual federated SGD on the LEAF-style CNN) with
the PON round simulator. Learning dynamics (accuracy vs round — Fig 2a) come
from real training; wall-clock training time (Fig 2b/3, the 36% saving)
comes from rounds × simulated synchronisation time.

Network timing backends (``FLNetworkCoSim.run``):

* ``"timeline"`` (default) — the whole training timeline advances as ONE
  stacked simulation on ``repro.net.timeline``: per-round client sets
  become membership masks over the union workload, per-round (possibly
  compression-dependent) upload sizes become the schedule's ``m_ud_bits``,
  and every round × timing-seed runs concurrently on the engine's batch
  axis with counter-keyed arrival streams.
* ``"per_round"`` — the PR 2 loop: one engine call per round, with the
  paper's observation that a fixed client set reuses its timing (the BS
  slice is recomputed only on membership change) expressed as a cache.

Deadline/async co-simulation (``mode="sync"`` with ``deadline_s``, or
``mode="async"``): timing and learning *couple* — who arrives in each
aggregation event, how stale, and with what served fraction is decided
by the network simulation, so the net timeline runs first and then
drives the training loop update by update. Deferred and async-straggler
updates apply staleness-weighted (``1/sqrt(1+τ)``, FedBuff), dropped
updates never apply, and partial updates apply scaled by the served
fraction — the Fig. 2a-style accuracy-vs-wall-clock comparison across
sync/drop/defer/partial/async under both DBA policies.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace as _dc_replace
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.slicing import ClientProfile
from repro.faults import FaultSchedule, RetryPolicy
from repro.fl.server import CPSServer
from repro.net.api import SweepSpec, simulate
from repro.net.engine import SweepCase
from repro.net.jobs import JobSpec
from repro.net.multi_pon import MultiPonTopology
from repro.net.sim import FLRoundWorkload, PONConfig
from repro.net.timeline import TimelineSchedule


@dataclass
class CoSimConfig:
    policy: str = "bs"              # "bs" | "fcfs"
    total_load: float = 0.8
    model_bits: float = 26.416e6    # global model size (fp32 downlink)
    upload_bits: Optional[float] = None  # per-client M_i^UD; None = model_bits
    pon: PONConfig = field(default_factory=PONConfig)
    timing_seeds: int = 2           # average the net-sim over this many seeds
    # several wavelength/OLT segments sharing a CPS uplink: ``pon``
    # then describes ONE segment and clients spread across
    # n_pons * pon.n_onus ONUs (None = single PON)
    topology: Optional[MultiPonTopology] = None
    # observability hub (repro.obs.Collector) threaded into every
    # network simulation this co-sim drives; None (the default) leaves
    # all outputs bitwise identical to an uninstrumented run
    collector: Optional[object] = None
    # deterministic fault injection (repro.faults): dropout/loss faults
    # and quorum aggregation need the *coupled* deadline/async path
    # (who retries/arrives is an event); outage-only faults also thread
    # into the decoupled timeline timing
    faults: Optional[FaultSchedule] = None
    retry: Optional[RetryPolicy] = None
    quorum_frac: Optional[float] = None
    # multi-tenant contention: competitor jobs (repro.net.jobs.JobSpec,
    # job_id >= 1) sharing the PON/CPS with this FL task, plus the
    # ClientProfiles backing their client ids; the primary task becomes
    # job 0 and every round's capacity is split by ``fairness``
    jobs: Optional[Tuple[JobSpec, ...]] = None
    job_clients: Optional[Tuple[ClientProfile, ...]] = None
    fairness: str = "maxmin"

    @classmethod
    def from_fed_model(cls, model_cfg, compress: str = "int8", **kw):
        """Size the slice from the real sharded update payload.

        Instead of the paper's hard-coded CNN constant, ``model_bits``
        becomes the fp32 wire size of the global model (the server's
        full-precision downlink broadcast) and ``upload_bits`` the size
        of one pod's *compressed* upload
        (``repro.dist.stepfns.fed_update_bits``) — so slice provisioning
        tracks whatever architecture/compression the pods actually
        train.
        """
        from repro.dist.stepfns import fed_update_bits  # avoid import cycle

        return cls(
            model_bits=float(fed_update_bits(model_cfg, "none")),
            upload_bits=float(fed_update_bits(model_cfg, compress)),
            **kw,
        )


@dataclass
class CoSimResult:
    rounds: List[dict]
    total_time_s: float
    sync_time_s: float              # steady-state per-round sync time
    policy: str
    load: float

    def time_to_metric(self, target: float) -> Optional[float]:
        """Wall-clock until eval_metric >= target (None if never)."""
        t = 0.0
        for r in self.rounds:
            t += r["sync_time_s"]
            if r["eval_metric"] is not None and r["eval_metric"] >= target:
                return t
        return None


class FLNetworkCoSim:
    def __init__(self, server: CPSServer, cfg: CoSimConfig):
        self.server = server
        self.cfg = cfg
        self._timing_cache: Dict[Tuple, float] = {}
        self._update_bits_from_compression = False
        self._collector = cfg.collector

    def _jobs_bundle(
        self, clients: List[ClientProfile],
    ) -> Tuple[List[ClientProfile], Optional[tuple]]:
        """(workload clients incl. tenant clients, full jobs tuple) —
        the primary task becomes job 0 over the server's clients."""
        if self.cfg.jobs is None:
            return clients, None
        primary = JobSpec(
            job_id=0,
            clients=tuple(sorted(c.client_id for c in clients)),
            model_bits=float(self.cfg.model_bits),
        )
        return (clients + list(self.cfg.job_clients or ()),
                (primary,) + tuple(self.cfg.jobs))

    def _round_sync_time(self, clients: List[ClientProfile]) -> float:
        # the key must pin every cfg field the timing depends on —
        # model_bits/upload_bits included, or mutating cfg between
        # run() calls on a reused co-sim would serve stale timings
        key = (
            self.cfg.policy,
            round(self.cfg.total_load, 6),
            self.cfg.model_bits,
            self.cfg.upload_bits,
            self.cfg.pon,
            self.cfg.topology,
            self.cfg.jobs,
            self.cfg.job_clients,
            self.cfg.fairness,
            tuple(sorted((c.client_id, round(c.t_ud, 6), c.m_ud_bits)
                         for c in clients)),
        )
        if key not in self._timing_cache:
            wl_clients, jobs = self._jobs_bundle(clients)
            wl = FLRoundWorkload(
                clients=wl_clients, model_bits=self.cfg.model_bits
            )
            # all timing seeds run as one stacked engine simulation
            results = simulate(SweepSpec(
                cases=tuple(
                    SweepCase(workload=wl, load=self.cfg.total_load,
                              policy=self.cfg.policy, seed=s,
                              topology=self.cfg.topology, jobs=jobs,
                              fairness=self.cfg.fairness)
                    for s in range(self.cfg.timing_seeds)
                ),
                pon=self.cfg.pon,
            ), collector=self._collector)
            # multi-tenant rounds gate on the PRIMARY job's sync time —
            # competitor jobs contend for capacity but do not hold this
            # task's aggregation open
            self._timing_cache[key] = float(np.mean([
                r.sync_time if jobs is None
                else r.job_stats[0].sync_time
                for r in results
            ]))
        return self._timing_cache[key]

    def _client_profiles(
        self, m_bits: Optional[float] = None,
    ) -> Tuple[List[ClientProfile], float]:
        if m_bits is None:
            m_bits = (
                self.cfg.upload_bits
                if self.cfg.upload_bits is not None
                else self.cfg.model_bits
            )
        profiles = [
            ClientProfile(
                client_id=c.client_id,
                t_ud=c.t_ud_s,
                t_dl=0.0,
                m_ud_bits=m_bits,
                distance_m=c.distance_m,
            )
            for c in self.server.clients
        ]
        return profiles, float(m_bits)

    def _round_profiles(self, log) -> Tuple[List[ClientProfile], float]:
        m_bits = None
        if self._update_bits_from_compression and log.n_arrived:
            m_bits = log.update_bits / max(log.n_arrived, 1)
        return self._client_profiles(m_bits)

    def _timeline_sync_times(
        self, per_round: List[List[ClientProfile]],
        m_bits: List[float],
    ) -> np.ndarray:
        """Per-round sync times, averaged over timing seeds, from ONE
        stacked multi-round simulation: the union of all rounds' clients
        forms the workload, per-round participation the membership
        mask, per-round upload sizes the schedule's ``m_ud_bits``."""
        R = len(per_round)
        union: Dict[int, ClientProfile] = {}
        for profs in per_round:
            for p in profs:
                union.setdefault(p.client_id, p)
        ids = sorted(union)
        if self.cfg.jobs is not None:
            # multi-tenant timelines take a plain schedule (per-round
            # membership/size rewrites are single-tenant features), so
            # the client set and upload size must be static across
            # rounds — per-job cadence goes through JobSpec instead
            static = all(
                {p.client_id for p in profs} == set(ids)
                for profs in per_round
            ) and len({float(b) for b in m_bits}) <= 1
            if not static or self.cfg.faults is not None:
                raise ValueError(
                    "multi-tenant co-simulation needs a static client "
                    "set, uniform upload size and no fault schedule "
                    "on the decoupled timeline backend; use "
                    "backend='per_round' for varying rounds"
                )
            wl_clients, jobs = self._jobs_bundle([union[c] for c in ids])
            wl = FLRoundWorkload(
                clients=wl_clients, model_bits=self.cfg.model_bits,
            )
            results = simulate(SweepSpec(
                cases=tuple(
                    SweepCase(workload=wl, load=self.cfg.total_load,
                              policy=self.cfg.policy, seed=s,
                              topology=self.cfg.topology, jobs=jobs,
                              fairness=self.cfg.fairness)
                    for s in range(self.cfg.timing_seeds)
                ),
                pon=self.cfg.pon,
                schedule=TimelineSchedule(n_rounds=R),
            ), collector=self._collector)
            return np.mean([
                [rnd.job_sync[0] for rnd in r.rounds] for r in results
            ], axis=0)
        pos = {cid: j for j, cid in enumerate(ids)}
        membership = np.zeros((R, len(ids)), bool)
        for r, profs in enumerate(per_round):
            for p in profs:
                membership[r, pos[p.client_id]] = True
        wl = FLRoundWorkload(
            clients=[union[c] for c in ids],
            model_bits=self.cfg.model_bits,
        )
        schedule = TimelineSchedule(
            n_rounds=R, membership=membership,
            m_ud_bits=np.asarray(m_bits),
            faults=self.cfg.faults,
        )
        results = simulate(SweepSpec(
            cases=tuple(
                SweepCase(workload=wl, load=self.cfg.total_load,
                          policy=self.cfg.policy, seed=s,
                          topology=self.cfg.topology)
                for s in range(self.cfg.timing_seeds)
            ),
            pon=self.cfg.pon,
            schedule=schedule,
        ), collector=self._collector)
        return np.mean([r.sync_times for r in results], axis=0)

    def _run_coupled(
        self,
        n_rounds: int,
        eval_fn: Optional[Callable],
        deadline_s,
        deadline_policy: str,
        buffer_k: Optional[int],
    ) -> CoSimResult:
        """Deadline/async co-simulation: the network decides per round
        who arrives (and how stale / how complete), the training loop
        follows.

        Every client participates each round unless its previous upload
        is still in flight (a deferred or async straggler — it idles
        until the stale update lands, then re-enters fresh). Fresh
        participants train against the global model at their entry
        round (a ``failure_prob`` roll can kill the update, exactly as
        in the sync path); their decoded update applies at the
        aggregation event the network delivers it to, discounted by
        staleness and served fraction
        (``fl.aggregation.fedbuff_merge``).

        Who arrives in which round is an *event*, not an average, so
        the coupled path follows one arrival realization —
        ``timing_seeds`` must be 1 (the decoupled path averages sync
        times over seeds; arrival sets cannot be averaged).

        Fault injection (``cfg.faults``) rides the same timeline: a
        dropout/loss victim's trained update stays pending while its
        retransmission is in flight (it does NOT retrain — the retry
        re-sends the same payload), a ``gave_up`` client abandons the
        pending update and trains fresh at its next entry, and
        ``cfg.quorum_frac`` gates each aggregation event
        (``CPSServer.apply_updates`` degrades to the previous global
        model below quorum).
        """
        if self.cfg.timing_seeds != 1:
            raise ValueError(
                "coupled deadline/async co-simulation follows one "
                "arrival realization; set timing_seeds=1 (who arrives "
                "per round is an event, not an averageable time)"
            )
        profiles, _ = self._client_profiles()
        wl = FLRoundWorkload(
            clients=profiles, model_bits=self.cfg.model_bits
        )
        schedule = TimelineSchedule(
            n_rounds=n_rounds, deadline_s=deadline_s,
            deadline_policy=deadline_policy, buffer_k=buffer_k,
            faults=self.cfg.faults, retry=self.cfg.retry,
            quorum_frac=self.cfg.quorum_frac,
        )
        net = simulate(SweepSpec(
            cases=(SweepCase(workload=wl, load=self.cfg.total_load,
                             policy=self.cfg.policy, seed=0,
                             topology=self.cfg.topology),),
            pon=self.cfg.pon,
            schedule=schedule,
        ), collector=self._collector)[0]
        by_id = {c.client_id: c for c in self.server.clients}
        pending: Dict[int, "PendingUpdate"] = {}
        rounds = []
        total_time = 0.0
        for rnd in net.rounds:
            fresh = sorted(set(rnd.ul_bits) - set(pending))
            for cid in fresh:
                # a failed client (same roll as the sync path) uploads
                # bits the network still carries, but its update is
                # lost — it contributes nothing when it "arrives"
                pending[cid] = self.server.train_client_update(
                    by_id[cid], self.server.global_params,
                )
            items = []
            for cid in rnd.arrived:
                u = pending.pop(cid)
                if u is not None:
                    items.append((u, rnd.staleness.get(cid, 0), 1.0))
            for cid in sorted(rnd.partial):
                u = pending.pop(cid)
                frac = rnd.partial[cid]
                if u is not None and frac > 0.0:
                    items.append((u, 0, frac))
            for cid in rnd.dropped:
                pending.pop(cid, None)
            # fault outcomes: failed (dropout) and lost (corrupted)
            # clients keep their trained update pending — the retry
            # re-sends the same payload; a gave_up client abandons it
            for cid in rnd.gave_up:
                pending.pop(cid, None)
            log = self.server.apply_updates(
                items, eval_fn=eval_fn,
                n_expected=(len(rnd.ul_bits)
                            if self.cfg.quorum_frac is not None else None),
                quorum_frac=self.cfg.quorum_frac,
            )
            log.sync_time_s = rnd.sync_time
            total_time += rnd.sync_time
            if self._collector is not None:
                self._collector.event(
                    "fl_round", mode="coupled", round=log.round_index,
                    sync_time_s=rnd.sync_time, n_arrived=log.n_arrived,
                    n_deferred=len(rnd.deferred),
                    n_dropped=len(rnd.dropped),
                    n_partial=len(rnd.partial),
                    n_failed=len(rnd.failed),
                    n_lost=len(rnd.lost),
                    quorum_met=rnd.quorum_met,
                    payload_bits=float(sum(rnd.ul_bits.values())),
                )
            rounds.append(
                {
                    "round": log.round_index,
                    "eval_metric": log.eval_metric,
                    "mean_loss": log.mean_loss,
                    "sync_time_s": rnd.sync_time,
                    "n_arrived": log.n_arrived,
                    "staleness": dict(rnd.staleness),
                    "n_failed": len(rnd.failed),
                    "n_lost": len(rnd.lost),
                    "quorum_met": log.quorum_met,
                }
            )
        return CoSimResult(
            rounds=rounds,
            total_time_s=total_time,
            sync_time_s=rounds[-1]["sync_time_s"] if rounds else 0.0,
            policy=self.cfg.policy,
            load=self.cfg.total_load,
        )

    def run(
        self,
        n_rounds: int,
        eval_fn: Optional[Callable] = None,
        update_bits_from_compression: bool = False,
        backend: str = "timeline",
        mode: str = "sync",
        deadline_s=None,
        deadline_policy: str = "defer",
        async_buffer: Optional[int] = None,
        collector=None,
        spec: Optional[SweepSpec] = None,
    ) -> CoSimResult:
        """Train ``n_rounds`` rounds and attach simulated network timing.

        ``spec`` (``repro.net.SweepSpec``, optional) re-points the
        network side at a spec template: its single case supplies
        (policy, load, topology, fairness) and ``spec.pon`` the PON
        config — the case's workload is replaced by the server's
        clients each round, and the co-sim builds its own schedule
        from ``n_rounds`` (schedule-bearing specs are rejected).

        ``backend="timeline"`` (default) resolves all rounds' timings in
        one stacked multi-round simulation after training;
        ``backend="per_round"`` keeps the PR 2 loop (one engine call per
        round, cached by client set) as the reference.

        ``mode="async"`` (FedBuff: each aggregation fires at the
        ``async_buffer``-th completed upload; default half the clients)
        or a ``deadline_s`` with a ``deadline_policy`` switch to the
        *coupled* co-simulation, where simulated arrival times decide
        which updates reach each aggregation event, how stale, and how
        complete — see :meth:`_run_coupled`. Compression-measured
        upload sizes (``update_bits_from_compression``) are a
        decoupled-path feature only.

        ``collector`` (``repro.obs.Collector``) overrides
        ``cfg.collector`` for this run; either turns on metrics in
        every network simulation the co-sim drives plus per-round
        ``fl_round`` events. ``None`` everywhere is bitwise identical
        to an uninstrumented run.
        """
        from repro.obs.trace import maybe_span

        if collector is not None:
            self._collector = collector
        if spec is not None:
            spec.validate()
            if spec.schedule is not None:
                raise ValueError(
                    "the co-sim builds its own schedule from "
                    "n_rounds; pass a schedule-free spec"
                )
            if len(spec.cases) != 1:
                raise ValueError(
                    "co-sim spec needs exactly one template case (its "
                    "workload is replaced by the server's clients)"
                )
            case = spec.cases[0]
            self.cfg = _dc_replace(
                self.cfg, policy=case.policy, total_load=case.load,
                topology=case.topology, fairness=case.fairness,
                pon=spec.pon if spec.pon is not None else self.cfg.pon,
            )
            self._timing_cache.clear()
        if backend not in ("timeline", "per_round"):
            raise ValueError(f"unknown backend {backend!r}")
        if mode not in ("sync", "async"):
            raise ValueError(f"unknown mode {mode!r}")
        if async_buffer is not None:
            # an explicit buffer IS the async request (mirrors the CLI,
            # where --async-buffer alone enables FedBuff); combining it
            # with a deadline fails in TimelineSchedule's validation
            mode = "async"
        coupled = mode == "async" or deadline_s is not None
        if coupled and self.cfg.jobs is not None:
            raise ValueError(
                "multi-tenant contention (cfg.jobs) takes per-job "
                "deadlines (JobSpec.deadline_s, fairness='deadline'); "
                "round-level deadline/async coupling is single-tenant"
            )
        if not coupled:
            if (self.cfg.faults is not None
                    and self.cfg.faults.couples_rounds):
                raise ValueError(
                    "dropout/loss fault injection decides who retries "
                    "and who arrives per round — an event, not a "
                    "timing average; use the coupled path (deadline_s "
                    "or mode='async'). Outage-only faults are fine "
                    "decoupled."
                )
            if self.cfg.quorum_frac is not None:
                raise ValueError(
                    "quorum aggregation gates per-round arrivals; use "
                    "the coupled path (deadline_s, per "
                    "TimelineSchedule's quorum validation)"
                )
        if coupled:
            if update_bits_from_compression:
                raise ValueError(
                    "update_bits_from_compression needs the decoupled "
                    "path; coupled deadline/async timing runs before "
                    "training"
                )
            if mode == "async" and async_buffer is None:
                async_buffer = max(1, len(self.server.clients) // 2)
            return self._run_coupled(
                n_rounds, eval_fn, deadline_s, deadline_policy,
                async_buffer if mode == "async" else None,
            )
        self._update_bits_from_compression = update_bits_from_compression
        rounds = []
        per_round_profiles: List[List[ClientProfile]] = []
        per_round_bits: List[float] = []
        sync = 0.0
        total_time = 0.0
        for _ in range(n_rounds):
            with maybe_span(self._collector, "fl.train_round"):
                log = self.server.run_round(eval_fn=eval_fn)
            profiles, m_bits = self._round_profiles(log)
            per_round_profiles.append(profiles)
            per_round_bits.append(m_bits)
            if backend == "per_round":
                sync = self._round_sync_time(profiles)
                log.sync_time_s = sync
                total_time += sync
            if self._collector is not None:
                self._collector.event(
                    "fl_round", mode="sync", round=log.round_index,
                    n_arrived=log.n_arrived,
                    payload_bits=float(m_bits) * log.n_arrived,
                )
            rounds.append(
                {
                    "round": log.round_index,
                    "eval_metric": log.eval_metric,
                    "mean_loss": log.mean_loss,
                    "sync_time_s": sync,
                    "n_arrived": log.n_arrived,
                }
            )
        if backend == "timeline" and rounds:
            sync_times = self._timeline_sync_times(
                per_round_profiles, per_round_bits
            )
            for entry, log, s in zip(rounds, self.server.history[-len(
                    rounds):], sync_times):
                entry["sync_time_s"] = float(s)
                log.sync_time_s = float(s)
            total_time = float(sync_times.sum())
            sync = float(sync_times[-1])
        return CoSimResult(
            rounds=rounds,
            total_time_s=total_time,
            sync_time_s=sync,
            policy=self.cfg.policy,
            load=self.cfg.total_load,
        )
