"""Multi-tenant jobs (``repro.net.jobs``): engine vs the cycle-level
oracle, fairness-split properties, cadenced timelines and the
single-job bitwise pin.

The batched engine's jobs path must reproduce the cycle-by-cycle dict
oracle (``simulate_jobs_round_reference``) at rtol 1e-6 across both
DBA policies, all three fairness policies, offset job counts and
multi-PON topologies — both sides consume the identical counter
streams and the identical ``job_fair_split`` arithmetic, so only the
cycle sequencing can drift.  A degenerate all-single-job sweep must
normalise to the plain single-tenant path bit-for-bit (pinned at the
PR 3 operating point).
"""
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    JobSpec,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    job_fair_split,
    make_competing_jobs,
    simulate,
    simulate_jobs_round_reference,
)

CFG = PONConfig(n_onus=8, line_rate_bps=1e9)

OP_POINT_SYNC = 5.058100000000024     # PR 3 Fig. 2b 0.8-load pin


def _clients(ids, seed=0, m_lo=1e5, m_hi=1e6):
    rng = np.random.default_rng(seed)
    return [
        ClientProfile(client_id=int(i),
                      t_ud=float(rng.uniform(0.05, 0.5)), t_dl=0.0,
                      m_ud_bits=float(rng.uniform(m_lo, m_hi)))
        for i in ids
    ]


def _mk_jobs(ids, n_jobs, weights=None, deadlines=None, cadence=None):
    """Round-robin partition of ``ids`` into ``n_jobs`` JobSpecs."""
    jobs = []
    for j in range(n_jobs):
        cad = cadence[j] if cadence else (1, 0)
        jobs.append(JobSpec(
            job_id=j,
            clients=tuple(i for k, i in enumerate(ids) if k % n_jobs == j),
            model_bits=4e5 * (j + 1),
            weight=weights[j] if weights else 1.0,
            deadline_s=deadlines[j] if deadlines else None,
            period=cad[0], phase=cad[1],
        ))
    return tuple(jobs)


def _mk_case(n_clients=6, n_jobs=2, policy="bs", fairness="maxmin",
             topology=None, load=0.6, seed=0, **job_kw):
    clients = _clients(range(n_clients), seed=seed)
    jobs = _mk_jobs([c.client_id for c in clients], n_jobs, **job_kw)
    wl = FLRoundWorkload(clients=clients, model_bits=4e5)
    return SweepCase(workload=wl, load=load, policy=policy, seed=seed,
                     topology=topology, jobs=jobs, fairness=fairness)


def _assert_parity(ref, eng, rtol=1e-6):
    for name in ("dl_done", "ready", "ul_done"):
        a, b = getattr(ref, name), getattr(eng, name)
        assert set(a) == set(b)
        for cid in a:
            assert b[cid] == pytest.approx(a[cid], rel=rtol, abs=1e-12), (
                f"{name}[{cid}]: oracle={a[cid]} engine={b[cid]}"
            )
    assert eng.sync_time == pytest.approx(ref.sync_time, rel=rtol)
    assert set(eng.job_stats) == set(ref.job_stats)
    for jid, rj in ref.job_stats.items():
        ej = eng.job_stats[jid]
        assert ej.sync_time == pytest.approx(rj.sync_time, rel=rtol)
        assert ej.n_clients == rj.n_clients
        for tier in ("onu_done", "olt_done"):
            ra, ea = getattr(rj, tier), getattr(ej, tier)
            assert set(ra) == set(ea)
            for k in ra:
                assert ea[k] == pytest.approx(ra[k], rel=rtol,
                                              abs=1e-12)


class TestEngineOracleParity:
    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("fairness", ["maxmin", "weighted",
                                          "deadline"])
    @pytest.mark.parametrize("n_jobs", [2, 3])
    def test_single_pon(self, policy, fairness, n_jobs):
        kw = {}
        if fairness == "weighted":
            kw["weights"] = [1.0 + j for j in range(n_jobs)]
        if fairness == "deadline":
            kw["deadlines"] = [4.0 - j for j in range(n_jobs)]
        case = _mk_case(n_clients=6, n_jobs=n_jobs, policy=policy,
                        fairness=fairness, load=0.7, **kw)
        eng = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
        ref = simulate_jobs_round_reference(CFG, case)
        _assert_parity(ref, eng)

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("fairness", ["maxmin", "weighted"])
    def test_multi_pon_cps(self, policy, fairness):
        """2 PONs × 2 jobs contending on a tight CPS uplink."""
        # cps cap above the mean background offer (0.7 x 2 x 1e9)
        # but below the 2e9 aggregate: FL contends, nothing saturates
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=1.9e9)
        kw = {"weights": [1.0, 3.0]} if fairness == "weighted" else {}
        case = _mk_case(n_clients=8, n_jobs=2, policy=policy,
                        fairness=fairness, topology=topo, load=0.7,
                        **kw)
        eng = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
        ref = simulate_jobs_round_reference(CFG, case)
        _assert_parity(ref, eng)

    def test_batched_cases_match_solo_runs(self):
        """Stacking multi-job cases in one sweep changes nothing."""
        cases = [
            _mk_case(n_jobs=2, policy=p, fairness="maxmin", seed=s)
            for p in ("fcfs", "bs") for s in (0, 1)
        ]
        batched = simulate(SweepSpec(cases=tuple(cases), pon=CFG))
        for case, got in zip(cases, batched):
            solo = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
            assert got.sync_time == solo.sync_time
            assert got.ul_done == solo.ul_done

    def test_jit_backend_falls_back_to_numpy(self):
        """Multi-job sweeps silently clear use_jit: identical results."""
        case = _mk_case(n_jobs=2, policy="bs")
        a = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
        b = simulate(SweepSpec(cases=(case,), pon=CFG,
                               backend="jit"))[0]
        assert b.sync_time == a.sync_time
        assert b.ul_done == a.ul_done


class TestSingleJobNormalisation:
    def _op_case(self, jobs=None):
        rng = np.random.default_rng(42)
        t_uds = rng.uniform(1.0, 5.0, 128)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                          m_ud_bits=26.416e6)
            for i in range(12)
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=26.416e6)
        return SweepCase(workload=wl, load=0.8, policy="fcfs", seed=1,
                         jobs=jobs)

    def test_bitwise_pin(self):
        """An all-single-job sweep runs the plain path bit-for-bit."""
        jobs = (JobSpec(job_id=0, clients=tuple(range(12)),
                        model_bits=26.416e6),)
        plain = simulate(SweepSpec(cases=(self._op_case(),),
                                   pon=PONConfig(n_onus=128)))[0]
        tenant = simulate(SweepSpec(cases=(self._op_case(jobs),),
                                    pon=PONConfig(n_onus=128)))[0]
        assert plain.sync_time == OP_POINT_SYNC
        assert tenant.sync_time == OP_POINT_SYNC      # exact, no rtol
        assert tenant.ul_done == plain.ul_done
        assert tenant.job_stats is not None
        assert tenant.job_stats[0].sync_time == OP_POINT_SYNC
        assert tenant.job_stats[0].n_clients == 12

    def test_single_job_keeps_deadline_knobs(self):
        """Normalised single-job sweeps may use single-tenant knobs."""
        jobs = (JobSpec(job_id=0, clients=tuple(range(12)),
                        model_bits=26.416e6),)
        res = simulate(SweepSpec(cases=(self._op_case(jobs),),
                                 pon=PONConfig(n_onus=128),
                                 ul_deadline_s=4.0))[0]
        assert res.sync_time <= OP_POINT_SYNC


class TestJobFairSplit:
    def test_passthrough_under_cap(self):
        d = np.array([[1.0, 2.0, 3.0], [0.5, 0.0, 1.0]])
        for fairness in ("maxmin", "weighted", "deadline"):
            out = job_fair_split(d, 100.0, fairness,
                                 weights=[1.0, 2.0, 3.0],
                                 slack=[3.0, 2.0, 1.0])
            np.testing.assert_array_equal(out, d)

    def test_bounds_and_conservation(self):
        rng = np.random.default_rng(7)
        d = rng.uniform(0.0, 10.0, (20, 4))
        cap = rng.uniform(2.0, 25.0, 20)
        for fairness in ("maxmin", "weighted", "deadline"):
            out = job_fair_split(d, cap, fairness,
                                 weights=rng.uniform(0.5, 2.0, 4),
                                 slack=rng.uniform(0.0, 5.0, (20, 4)))
            assert np.all(out <= d + 1e-9)
            assert np.all(out >= -1e-12)
            assert np.all(out.sum(axis=1) <= cap + 1e-6)
            over = d.sum(axis=1) > cap
            got = out.sum(axis=1)[over]
            np.testing.assert_allclose(got, cap[over], rtol=1e-9)

    def test_unit_weights_bitwise_maxmin(self):
        rng = np.random.default_rng(3)
        d = rng.uniform(0.0, 10.0, (16, 3))
        cap = rng.uniform(2.0, 20.0, 16)
        a = job_fair_split(d, cap, "maxmin")
        b = job_fair_split(d, cap, "weighted",
                           weights=np.ones(3))
        np.testing.assert_array_equal(a, b)

    def test_weighted_shares_follow_weights(self):
        out = job_fair_split([10.0, 10.0], 6.0, "weighted",
                             weights=[1.0, 2.0])
        np.testing.assert_allclose(out, [2.0, 4.0], rtol=1e-12)

    def test_deadline_earliest_slack_first(self):
        out = job_fair_split([4.0, 4.0, 4.0], 6.0, "deadline",
                             slack=[3.0, 0.5, 1.0])
        np.testing.assert_allclose(out, [0.0, 4.0, 2.0], rtol=1e-12)

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown fairness"):
            job_fair_split([1.0], 1.0, "roundrobin")


class TestJobSpecAndHelpers:
    def test_cadence(self):
        job = JobSpec(job_id=1, clients=(0,), model_bits=1e5,
                      period=3, phase=2)
        assert [job.active_in(r) for r in range(7)] == [
            False, False, True, False, False, True, False
        ]

    def test_make_competing_jobs(self):
        jobs, profs = make_competing_jobs([0, 1, 2], 1e6, n_jobs=2,
                                          clients_each=2)
        assert [j.job_id for j in jobs] == [1, 2]
        assert jobs[0].clients == (3, 4)
        assert jobs[1].clients == (5, 6)
        assert all(j.model_bits == 5e5 for j in jobs)
        assert [p.client_id for p in profs] == [3, 4, 5, 6]

    def test_partition_validation(self):
        clients = _clients(range(4))
        wl = FLRoundWorkload(clients=clients, model_bits=4e5)
        overlap = (
            JobSpec(job_id=0, clients=(0, 1), model_bits=1e5),
            JobSpec(job_id=1, clients=(1, 2, 3), model_bits=1e5),
        )
        case = SweepCase(workload=wl, load=0.5, policy="fcfs",
                         jobs=overlap)
        with pytest.raises(ValueError, match="belongs to jobs"):
            simulate(SweepSpec(cases=(case,), pon=CFG))
        hole = (JobSpec(job_id=0, clients=(0, 1, 2), model_bits=1e5),)
        case = SweepCase(workload=wl, load=0.5, policy="fcfs",
                         jobs=hole)
        with pytest.raises(ValueError, match="partition"):
            simulate(SweepSpec(cases=(case,), pon=CFG))

    def test_multi_job_rejects_single_tenant_knobs(self):
        case = _mk_case(n_jobs=2)
        with pytest.raises(ValueError, match="per-job deadlines"):
            simulate(SweepSpec(cases=(case,), pon=CFG,
                               ul_deadline_s=1.0))


class TestArrivalShapeValidation:
    """PR 9 satellite: injected arrival matrices must span the full
    ``n_pons * n_onus`` ONU axis — both phases, with a clear error."""

    @pytest.mark.parametrize("field", ["dl_arrivals", "ul_arrivals"])
    def test_wrong_width_raises(self, field):
        clients = _clients(range(6))
        wl = FLRoundWorkload(clients=clients, model_bits=4e5)
        bad = np.zeros((5, CFG.n_onus))     # needs 2 * n_onus columns
        case = SweepCase(workload=wl, load=0.5, policy="fcfs",
                         topology=MultiPonTopology(n_pons=2),
                         **{field: bad})
        with pytest.raises(ValueError, match=r"n_pons \* n_onus"):
            simulate(SweepSpec(cases=(case,), pon=CFG))

    def test_right_width_accepted(self):
        clients = _clients(range(6))
        wl = FLRoundWorkload(clients=clients, model_bits=4e5)
        arr = np.zeros((5, 2 * CFG.n_onus))
        case = SweepCase(workload=wl, load=0.5, policy="fcfs",
                         topology=MultiPonTopology(n_pons=2),
                         dl_arrivals=arr, ul_arrivals=arr)
        res = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
        assert np.isfinite(res.sync_time)


class TestJobTimelines:
    def _spec(self, n_rounds=4, cadence=None, n_jobs=3):
        case = _mk_case(n_clients=6, n_jobs=n_jobs, policy="bs",
                        cadence=cadence)
        return SweepSpec(
            cases=(case,), pon=CFG,
            schedule=TimelineSchedule(n_rounds=n_rounds),
        )

    def test_cadenced_job_sync(self):
        """Offset cadences: jobs 1/2 alternate rounds; job 0 always."""
        spec = self._spec(cadence=[(1, 0), (2, 0), (2, 1)])
        tl = simulate(spec)[0]
        assert len(tl.rounds) == 4
        for r, rnd in enumerate(tl.rounds):
            expect = {0, 1} if r % 2 == 0 else {0, 2}
            assert set(rnd.job_sync) == expect
            assert all(t > 0.0 for t in rnd.job_sync.values())
            assert rnd.sync_time == pytest.approx(
                max(rnd.job_sync.values())
            )

    def test_rounds_match_single_round_runs(self):
        """Independent rounds: round r of the folded timeline equals a
        fresh single-round sweep at stream_round=r."""
        from dataclasses import replace

        case = _mk_case(n_clients=6, n_jobs=2, policy="fcfs")
        spec = SweepSpec(cases=(case,), pon=CFG,
                         schedule=TimelineSchedule(n_rounds=3))
        tl = simulate(spec)[0]
        for r in range(3):
            solo = simulate(SweepSpec(
                cases=(replace(case, stream_round=r),), pon=CFG,
            ))[0]
            assert tl.rounds[r].sync_time == solo.sync_time
            assert tl.rounds[r].job_sync == {
                jid: js.sync_time for jid, js in solo.job_stats.items()
            }

    def test_cadenced_round_matches_oracle(self):
        """A cadenced timeline round equals the oracle run on just
        that round's active jobs (filtered workload, stream_round=r)."""
        from dataclasses import replace

        case = _mk_case(n_clients=6, n_jobs=3, policy="fcfs",
                        cadence=[(1, 0), (2, 0), (2, 1)])
        tl = simulate(SweepSpec(
            cases=(case,), pon=CFG,
            schedule=TimelineSchedule(n_rounds=2),
        ))[0]
        for r in range(2):
            active = tuple(j for j in case.jobs if j.active_in(r))
            keep = {c for j in active for c in j.clients}
            wl = FLRoundWorkload(
                clients=[c for c in case.workload.clients
                         if c.client_id in keep],
                model_bits=case.workload.model_bits,
            )
            ref = simulate_jobs_round_reference(
                CFG, replace(case, workload=wl, jobs=active,
                             stream_round=r),
            )
            for jid, js in ref.job_stats.items():
                assert tl.rounds[r].job_sync[jid] == pytest.approx(
                    js.sync_time, rel=1e-6
                )

    def test_schedule_features_rejected(self):
        case = _mk_case(n_jobs=2)
        sched = TimelineSchedule(
            n_rounds=2, membership=np.ones((2, 6), bool),
        )
        with pytest.raises(ValueError, match="plain schedule"):
            simulate(SweepSpec(cases=(case,), pon=CFG,
                               schedule=sched))

    def test_mixed_sweep_rejected(self):
        tenant = _mk_case(n_jobs=2)
        plain = SweepCase(workload=tenant.workload, load=0.5,
                          policy="fcfs")
        with pytest.raises(ValueError, match="mix"):
            simulate(SweepSpec(
                cases=(tenant, plain), pon=CFG,
                schedule=TimelineSchedule(n_rounds=2),
            ))
