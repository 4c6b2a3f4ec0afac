"""PON network substrate: traffic, DBA engines, round + timeline sims.

The curated surface is ``__all__``: build a :class:`SweepSpec` and run
it with :func:`simulate`, the one entry point to the round and timeline
engines. The other ``simulate_*`` functions are the cycle-level parity
oracles. Everything else in the submodules is internal.
"""
from repro.faults import (
    FaultSchedule,
    RetryPolicy,
)
from repro.net.api import (
    SweepSpec,
    simulate,
)
from repro.net.dba import (
    DEFAULT_EFFICIENCY,
    FCFSBestEffort,
    FCFSLimitedService,
    OnuQueue,
    SlicedDBA,
)
from repro.net.engine import SweepCase
from repro.net.jobs import (
    FAIRNESS_POLICIES,
    JobRoundStats,
    JobSpec,
    job_fair_split,
    make_competing_jobs,
    simulate_jobs_round_reference,
)
from repro.net.multi_pon import (
    MultiPonTopology,
    cps_waterfill,
    pon_bg_rates,
    simulate_multi_pon_round,
)
from repro.net.sim import (
    FLRoundWorkload,
    PONConfig,
    RoundResult,
    simulate_round_reference,
)
from repro.net.timeline import (
    DEADLINE_POLICIES,
    TimelineResult,
    TimelineRound,
    TimelineSchedule,
    simulate_timeline_reference,
)
from repro.net.traffic import (
    PACKET_BITS,
    CounterSource,
    CounterStream,
    PoissonSource,
    PrecomputedSource,
    background_rate_for_load,
    burst_lambda,
    per_onu_sources,
)

__all__ = [
    # spec facade (the one engine entry point)
    "SweepSpec",
    "simulate",
    # sweep building blocks
    "SweepCase",
    "PONConfig",
    "FLRoundWorkload",
    "RoundResult",
    # multi-tenant jobs
    "FAIRNESS_POLICIES",
    "JobSpec",
    "JobRoundStats",
    "job_fair_split",
    "make_competing_jobs",
    "simulate_jobs_round_reference",
    # multi-PON topology
    "MultiPonTopology",
    "cps_waterfill",
    "pon_bg_rates",
    "simulate_multi_pon_round",
    # timelines
    "DEADLINE_POLICIES",
    "TimelineSchedule",
    "TimelineRound",
    "TimelineResult",
    "simulate_timeline_reference",
    # faults (re-export: timeline fault model)
    "FaultSchedule",
    "RetryPolicy",
    # single-PON cycle-level oracle
    "simulate_round_reference",
    # DBA engines
    "DEFAULT_EFFICIENCY",
    "FCFSBestEffort",
    "FCFSLimitedService",
    "OnuQueue",
    "SlicedDBA",
    # traffic sources
    "PACKET_BITS",
    "CounterSource",
    "CounterStream",
    "PoissonSource",
    "PrecomputedSource",
    "background_rate_for_load",
    "burst_lambda",
    "per_onu_sources",
]
