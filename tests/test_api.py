"""The SweepSpec facade (``repro.net.api``).

``simulate(spec)`` is the one entry point to the round and timeline
engines: every spec field must reach the driver it dispatches to, the
builders must compose specs without mutation, ``validate()`` must
reject malformed bundles with actionable errors, and the curated
``repro.net.__all__`` plus the job-aware stream keys are pinned.
"""
from dataclasses import replace

import numpy as np
import pytest

import repro.net as net
from repro.core.slicing import ClientProfile
from repro.kernels.traffic import ops
from repro.net import (
    FaultSchedule,
    FLRoundWorkload,
    JobSpec,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
)
from repro.obs import Collector, SpanTracer

CFG = PONConfig(n_onus=8, line_rate_bps=1e9)


def _clients(n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [
        ClientProfile(client_id=i,
                      t_ud=float(rng.uniform(0.05, 0.5)), t_dl=0.0,
                      m_ud_bits=float(rng.uniform(1e5, 1e6)))
        for i in range(n)
    ]


def _cases(n=6):
    wl = FLRoundWorkload(clients=_clients(n), model_bits=5e5)
    return tuple(
        SweepCase(workload=wl, load=0.6, policy=policy, seed=0)
        for policy in ("fcfs", "bs")
    )


def _assert_rounds_bitwise(a, b):
    """Sync times and per-client done times equal, NaN (cut) included."""
    for ra, rb in zip(a, b, strict=True):
        assert ra.sync_time == rb.sync_time
        for attr in ("dl_done", "ul_done"):
            da, db = getattr(ra, attr), getattr(rb, attr)
            assert list(da) == list(db)
            np.testing.assert_array_equal(list(da.values()),
                                          list(db.values()))


def _traced(run):
    """``run(collector)``'s result and the names of the host spans it
    recorded, the driver's footprint (less ``simulate``'s own span)."""
    col = Collector(tracer=SpanTracer())
    out = run(col)
    return out, [e["name"] for e in col.tracer.events
                 if e["name"] != "net.simulate"]


class TestSpecFieldsReachTheDriver:
    """Every ``SweepSpec`` field reaches the driver ``simulate`` calls:
    ``simulate(spec)`` is bitwise the internal driver called with that
    field as a keyword, and takes the same path (the same host spans).
    """

    @pytest.mark.parametrize("field,value", [
        ("t_round_hint", 0.2),
        ("max_t", 0.2),
        ("ul_deadline_s", 0.3),
        ("ul_outage_s", ((0.1, 0.2), (0.1, 0.2))),
        ("backend", "jit"),
    ], ids=["t_round_hint", "max_t", "ul_deadline_s", "ul_outage_s",
            "backend_jit"])
    def test_round_field(self, field, value):
        from repro.net.engine import _round_sweep

        cases = _cases()
        spec = SweepSpec(cases=cases, pon=CFG)
        got, got_spans = _traced(lambda col: simulate(
            replace(spec, **{field: value}), collector=col))
        want, want_spans = _traced(lambda col: _round_sweep(
            CFG, list(cases), collector=col, **{field: value}))
        _assert_rounds_bitwise(got, want)
        assert got_spans == want_spans
        # each value changes the run, so a field lost on the way shows
        base, base_spans = _traced(lambda col: simulate(spec,
                                                        collector=col))
        assert ([r.sync_time for r in got], got_spans) != (
            [r.sync_time for r in base], base_spans)

    @pytest.mark.parametrize("mode", ["auto", "folded", "sequential"])
    def test_timeline_mode(self, mode):
        from repro.net.timeline import _timeline_sweep

        cases = _cases()
        sched = TimelineSchedule(n_rounds=3)
        got, got_spans = _traced(lambda col: simulate(SweepSpec(
            cases=cases, pon=CFG, schedule=sched, mode=mode),
            collector=col))
        want, want_spans = _traced(lambda col: _timeline_sweep(
            CFG, list(cases), sched, mode=mode, collector=col))
        assert got_spans == want_spans
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert list(a.sync_times) == list(b.sync_times)
            assert a.total_time_s == b.total_time_s


class TestSpecKwargEquivalence:
    def test_explicit_cfg_overrides_spec_pon(self):
        cases = _cases()
        big = PONConfig(n_onus=8, line_rate_bps=2e9)
        spec = SweepSpec(cases=cases, pon=CFG)
        a = simulate(spec, big)
        b = simulate(SweepSpec(cases=cases, pon=big))
        assert [r.sync_time for r in a] == [r.sync_time for r in b]


class TestValidate:
    def test_needs_cases(self):
        with pytest.raises(ValueError, match="at least one case"):
            SweepSpec().validate()

    def test_case_type(self):
        with pytest.raises(TypeError, match=r"cases\[0\]"):
            SweepSpec(cases=("nope",)).validate()

    def test_bad_policy_and_fairness(self):
        wl = FLRoundWorkload(clients=_clients(), model_bits=5e5)
        bad = SweepCase(workload=wl, load=0.5, policy="edf")
        with pytest.raises(ValueError, match="unknown policy"):
            SweepSpec(cases=(bad,)).validate()
        bad = SweepCase(workload=wl, load=0.5, policy="bs",
                        fairness="lottery")
        with pytest.raises(ValueError, match="unknown fairness"):
            SweepSpec(cases=(bad,)).validate()

    def test_bad_mode_backend(self):
        with pytest.raises(ValueError, match="unknown mode"):
            SweepSpec(cases=_cases(),
                      schedule=TimelineSchedule(n_rounds=1),
                      mode="eager").validate()
        with pytest.raises(ValueError, match="unknown backend"):
            SweepSpec(cases=_cases(), backend="torch").validate()

    def test_mode_needs_schedule(self):
        with pytest.raises(ValueError, match="timeline knob"):
            SweepSpec(cases=_cases(), mode="folded").validate()

    def test_deadline_knobs_clash_with_schedule(self):
        with pytest.raises(ValueError, match="from the schedule"):
            SweepSpec(cases=_cases(),
                      schedule=TimelineSchedule(n_rounds=1),
                      ul_deadline_s=1.0).validate()

    def test_pon_type(self):
        with pytest.raises(TypeError, match="PONConfig"):
            SweepSpec(cases=_cases(), pon="gpon").validate()

    def test_simulate_rejects_non_spec(self):
        with pytest.raises(TypeError, match="SweepSpec"):
            simulate(list(_cases()))

    def test_job_errors_carry_case_index(self):
        wl = FLRoundWorkload(clients=_clients(4), model_bits=5e5)
        jobs = (JobSpec(job_id=0, clients=(0, 1), model_bits=1e5),)
        bad = SweepCase(workload=wl, load=0.5, policy="fcfs",
                        jobs=jobs)
        with pytest.raises(ValueError, match=r"cases\[0\]"):
            SweepSpec(cases=(bad,)).validate()


class TestBuilders:
    def test_single_job(self):
        spec = SweepSpec.single_job(_clients(4), 1e6, load=0.4,
                                    policy="fcfs", seed=3, pon=CFG)
        spec.validate()
        assert len(spec.cases) == 1
        case = spec.cases[0]
        assert (case.policy, case.load, case.seed) == ("fcfs", 0.4, 3)
        assert case.workload.model_bits == 1e6
        assert spec.pon is CFG

    def test_with_schedule_and_faults(self):
        sched = TimelineSchedule(n_rounds=4)
        faults = FaultSchedule(dropout_rate=0.1)
        spec = SweepSpec(cases=_cases()).with_schedule(sched)
        spec2 = spec.with_faults(faults)
        assert spec.schedule.faults is None      # frozen: no mutation
        assert spec2.schedule.faults is faults
        assert spec2.schedule.n_rounds == 4

    def test_with_faults_needs_schedule(self):
        with pytest.raises(ValueError, match="with_schedule"):
            SweepSpec(cases=_cases()).with_faults(
                FaultSchedule(dropout_rate=0.1)
            )

    def test_with_jobs(self):
        jobs = (
            JobSpec(job_id=0, clients=(0, 1, 2), model_bits=5e5),
            JobSpec(job_id=1, clients=(3, 4, 5), model_bits=2e5),
        )
        spec = SweepSpec(cases=_cases()).with_jobs(
            jobs, fairness="weighted"
        )
        spec.validate()
        assert all(c.jobs == jobs for c in spec.cases)
        assert all(c.fairness == "weighted" for c in spec.cases)


class TestCuratedSurface:
    def test_all_resolves(self):
        for name in net.__all__:
            assert not name.startswith("_")
            assert hasattr(net, name), name

    def test_key_names_exported(self):
        for name in ("SweepSpec", "simulate", "SweepCase", "JobSpec",
                     "JobRoundStats", "job_fair_split",
                     "FAIRNESS_POLICIES", "make_competing_jobs",
                     "simulate_jobs_round_reference",
                     "DEADLINE_POLICIES", "simulate_round_reference",
                     "simulate_timeline_reference"):
            assert name in net.__all__, name

    def test_internal_drivers_not_exported(self):
        assert "_round_sweep" not in net.__all__
        assert "_timeline_sweep" not in net.__all__
        # one engine entry point; every other simulate* is an oracle
        assert {n for n in dir(net) if n.startswith("simulate")} == {
            "simulate", "simulate_round_reference",
            "simulate_multi_pon_round", "simulate_jobs_round_reference",
            "simulate_timeline_reference",
        }


class TestJobStreamKeys:
    def test_job0_bitwise_legacy(self):
        for seed, phase, rnd, pon in ((7, 1, 3, 0), (3, 0, 5, 2)):
            legacy = ops.make_stream_key(seed, phase, rnd, pon=pon)
            keyed = ops.make_stream_key(seed, phase, rnd, pon=pon,
                                        job=0)
            assert np.array_equal(legacy, keyed)

    def test_pinned_fingerprints(self):
        pins = {
            (7, 1, 3, 0, 0): (7, 7),
            (7, 1, 3, 0, 1): (3266489916, 668265270),
            (7, 1, 3, 1, 2): (1375963586, 1798376440),
            (3, 0, 0, 0, 1): (3266489912, 668265263),
            (3, 0, 0, 0, 2): (2238012525, 1336530526),
        }
        for (seed, phase, rnd, pon, job), want in pins.items():
            key = ops.make_stream_key(seed, phase, rnd, pon=pon,
                                      job=job)
            assert tuple(int(x) for x in key) == want

    def test_jobs_get_distinct_streams(self):
        keys = {
            tuple(ops.make_stream_key(3, 1, 2, pon=1, job=j).tolist())
            for j in range(8)
        }
        assert len(keys) == 8
