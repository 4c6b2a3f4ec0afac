"""Async/stale FL rounds: deadline policies + FedBuff timeline.

Three things are pinned here:

* ``deadline_policy="defer"`` is the PR 3/4 deferral behaviour,
  bit-for-bit — including the Fig. 2b operating-point sync pin;
* drop / partial / async agree with the cycle-level reference oracle
  at rtol 1e-6 over both DBA policies and multi-PON topologies;
* the satellite bugfixes: ``TimelineSchedule`` defensively copies its
  caller's arrays, ``_round_view`` refuses to drop pending clients,
  and the co-sim timing cache keys on the payload sizes.
"""
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
    simulate_timeline_reference,
)
from repro.net.timeline import _round_view

CFG = PONConfig(n_onus=8, line_rate_bps=1e9)


def _clients(ids, seed=0, m_lo=1e5, m_hi=2e6):
    rng = np.random.default_rng(seed)
    return [
        ClientProfile(client_id=int(i),
                      t_ud=float(rng.uniform(0.05, 0.6)), t_dl=0.0,
                      m_ud_bits=float(rng.uniform(m_lo, m_hi)))
        for i in ids
    ]


def _wl(policy, seed=0):
    ids = range(6) if policy == "bs" else [0, 1, 5, 9, 17, 19]
    return FLRoundWorkload(clients=_clients(ids, seed), model_bits=1.5e6)


def _assert_equal(a, b, rtol=1e-6):
    for ra, rb in zip(a, b):
        assert np.allclose(ra.sync_times, rb.sync_times, rtol=rtol), (
            f"sync {ra.sync_times} vs {rb.sync_times}"
        )
        for x, y in zip(ra.rounds, rb.rounds):
            assert x.arrived == y.arrived
            assert x.staleness == y.staleness
            for name in ("ul_bits", "deferred", "dropped", "partial"):
                xd, yd = getattr(x, name), getattr(y, name)
                assert set(xd) == set(yd), (x.round_index, name)
                for cid, v in xd.items():
                    assert v == pytest.approx(yd[cid], rel=rtol, abs=2.0)


class TestPolicyParityVsOracle:
    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("dpolicy", ["drop", "partial"])
    def test_deadline_policies(self, policy, dpolicy):
        sched = TimelineSchedule(n_rounds=4, deadline_s=0.35,
                                 deadline_policy=dpolicy)
        cases = [SweepCase(workload=_wl(policy), load=0.6,
                           policy=policy, seed=5)]
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        cut = "dropped" if dpolicy == "drop" else "partial"
        assert sum(len(getattr(r, cut)) for r in eng[0].rounds) > 0, (
            "deadline chosen to force cutoffs"
        )
        _assert_equal(eng, ref)

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("buffer_k", [1, 3])
    def test_async_buffered(self, policy, buffer_k):
        sched = TimelineSchedule(n_rounds=4, buffer_k=buffer_k)
        cases = [SweepCase(workload=_wl(policy), load=0.6,
                           policy=policy, seed=5)]
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        _assert_equal(eng, ref)
        assert sum(len(r.deferred) for r in eng[0].rounds) > 0, (
            "buffer_k chosen to leave stragglers in flight"
        )

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_multi_pon_policies(self, policy):
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=1.8e9)
        cases = [SweepCase(workload=_wl(policy), load=0.4,
                           policy=policy, seed=5, topology=topo)]
        for sched in (
            TimelineSchedule(n_rounds=3, buffer_k=3),
            TimelineSchedule(n_rounds=3, deadline_s=0.35,
                             deadline_policy="partial"),
            TimelineSchedule(n_rounds=3, deadline_s=0.35,
                             deadline_policy="drop"),
        ):
            _assert_equal(
                simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched)),
                simulate_timeline_reference(CFG, cases, sched),
            )

    def test_folded_matches_sequential_for_drop_partial(self):
        for dpolicy in ("drop", "partial"):
            sched = TimelineSchedule(n_rounds=3, deadline_s=0.35,
                                     deadline_policy=dpolicy)
            cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                               policy="fcfs", seed=7)]
            _assert_equal(
                simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                   mode="folded")),
                simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                   mode="sequential")),
                rtol=1e-12,
            )


class TestDeferUnchanged:
    """``deadline_policy="defer"`` must be the PR 3/4 deferral,
    bit-for-bit."""

    def test_default_policy_is_defer(self):
        assert TimelineSchedule(n_rounds=1).deadline_policy == "defer"

    def test_explicit_defer_identical_to_default(self):
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        a = simulate(SweepSpec(
            cases=cases, pon=CFG,
            schedule=TimelineSchedule(n_rounds=3, deadline_s=0.35),
        ))
        b = simulate(SweepSpec(
            cases=cases, pon=CFG,
            schedule=TimelineSchedule(n_rounds=3, deadline_s=0.35,
                                      deadline_policy="defer"),
        ))
        for x, y in zip(a[0].rounds, b[0].rounds):
            assert x.sync_time == y.sync_time
            assert x.ul_bits == y.ul_bits
            assert x.deferred == y.deferred

    def test_operating_point_sync_pinned(self):
        """The Fig. 2b 0.8-load cell through the defer-policy timeline
        (deadline wide enough that nothing defers) reproduces the
        pinned sync bit-for-bit."""
        rng = np.random.default_rng(42)
        t_uds = rng.uniform(1.0, 5.0, 128)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                          m_ud_bits=26.416e6)
            for i in range(12)
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=26.416e6)
        cfg = PONConfig(n_onus=128)
        case = SweepCase(workload=wl, load=0.8, policy="fcfs", seed=1)
        for sched in (
            TimelineSchedule(n_rounds=1),
            TimelineSchedule(n_rounds=1, deadline_s=30.0,
                             deadline_policy="defer"),
            TimelineSchedule(n_rounds=1, deadline_s=30.0,
                             deadline_policy="drop"),
        ):
            res = simulate(SweepSpec(cases=[case], pon=cfg,
                                     schedule=sched))[0]
            assert res.rounds[0].sync_time == pytest.approx(
                5.058100000000024, abs=1e-9
            )


class TestPolicySemantics:
    def _run(self, dpolicy, rounds=4, deadline=0.35):
        sched = TimelineSchedule(n_rounds=rounds, deadline_s=deadline,
                                 deadline_policy=dpolicy)
        wl = _wl("fcfs")
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=0.6, policy="fcfs",
                             seed=7)],
            pon=CFG, schedule=sched,
        ))[0]
        return wl, res

    def test_drop_discards_and_reenters_fresh(self):
        wl, res = self._run("drop")
        m_ud = {c.client_id: c.m_ud_bits for c in wl.clients}
        saw_drop = False
        for r in res.rounds:
            assert r.deferred == {}
            for cid, bits in r.dropped.items():
                saw_drop = True
                assert bits > 0.0
            # every participant starts from its full update each round
            for cid, served in r.ul_bits.items():
                assert served <= m_ud[cid] + 2.0
        assert saw_drop

    def test_partial_fraction_is_served_over_total(self):
        wl, res = self._run("partial")
        m_ud = {c.client_id: c.m_ud_bits for c in wl.clients}
        saw_partial = False
        for r in res.rounds:
            assert r.deferred == {} and r.dropped == {}
            for cid, frac in r.partial.items():
                saw_partial = True
                assert 0.0 <= frac < 1.0
                assert frac == pytest.approx(
                    r.ul_bits[cid] / m_ud[cid], rel=1e-9
                )
        assert saw_partial

    def test_async_fires_at_kth_arrival(self):
        k = 2
        sched = TimelineSchedule(n_rounds=4, buffer_k=k)
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=_wl("fcfs"), load=0.6,
                             policy="fcfs", seed=7)],
            pon=CFG, schedule=sched,
        ))[0]
        for r in res.rounds:
            pending = len(r.ul_bits)
            assert len(r.arrived) >= min(k, pending)
            # the aggregation fires at the k-th completion: its time
            # bounds the round (modulo the aggregation term and the
            # final cycle completing)
            if r.deferred:
                times = sorted(r.result.ul_done[c] for c in r.arrived)
                assert r.sync_time == pytest.approx(times[k - 1])

    def test_async_staleness_counts_rounds_in_flight(self):
        sched = TimelineSchedule(n_rounds=5, buffer_k=1)
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=_wl("fcfs"), load=0.6,
                             policy="fcfs", seed=7)],
            pon=CFG, schedule=sched,
        ))[0]
        # with k=1 the slowest clients stay in flight across several
        # aggregations and must arrive with staleness > 0
        stale = [t for r in res.rounds for t in r.staleness.values()]
        assert max(stale) > 0
        for r in res.rounds:
            for cid in r.arrived:
                assert r.staleness[cid] >= 0

    def test_async_conserves_upload_bits(self):
        wl = _wl("fcfs")
        sched = TimelineSchedule(n_rounds=5, buffer_k=2)
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=0.6, policy="fcfs",
                             seed=7)],
            pon=CFG, schedule=sched,
        ))[0]
        m_ud = {c.client_id: c.m_ud_bits for c in wl.clients}
        served = {cid: 0.0 for cid in m_ud}
        done = {cid: 0 for cid in m_ud}
        for r in res.rounds:
            for cid, bits in r.ul_bits.items():
                served[cid] += bits
            for cid in r.arrived:
                done[cid] += 1
        for cid in m_ud:
            leftover = served[cid] - done[cid] * m_ud[cid]
            assert -2.0 <= leftover <= m_ud[cid]


class TestScheduleValidation:
    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="deadline_policy"):
            TimelineSchedule(n_rounds=1, deadline_s=1.0,
                             deadline_policy="teleport")

    def test_policy_requires_deadline(self):
        with pytest.raises(ValueError, match="needs"):
            TimelineSchedule(n_rounds=1, deadline_policy="drop")

    def test_async_excludes_deadline(self):
        with pytest.raises(ValueError, match="buffer_k"):
            TimelineSchedule(n_rounds=1, deadline_s=1.0, buffer_k=2)

    def test_async_rejects_folded(self):
        with pytest.raises(ValueError, match="folded"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=_wl("fcfs"), load=0.5,
                                 policy="fcfs", seed=0)],
                pon=CFG, schedule=TimelineSchedule(n_rounds=2, buffer_k=2),
                mode="folded",
            ))

    def test_defer_deadline_rejects_folded(self):
        with pytest.raises(ValueError, match="folded"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=_wl("fcfs"), load=0.5,
                                 policy="fcfs", seed=0)],
                pon=CFG,
                schedule=TimelineSchedule(n_rounds=2, deadline_s=0.5),
                mode="folded",
            ))


class TestScheduleDefensiveCopies:
    """Satellite bugfix: mutating the caller's arrays after
    construction must not desync folded vs reference results (both
    must see the construction-time values)."""

    def test_membership_and_m_ud_copied(self):
        memb = np.ones((3, 6), bool)
        m_ud = np.full(3, 5e5)
        dl = np.array([0.35, 0.35, 0.35])
        sched = TimelineSchedule(n_rounds=3, membership=memb,
                                 m_ud_bits=m_ud, deadline_s=dl)
        cases = [SweepCase(workload=_wl("fcfs"), load=0.5,
                           policy="fcfs", seed=3)]
        before = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        # caller mutates everything after construction
        memb[:] = False
        m_ud[:] = 1.0
        dl[:] = 1e-4
        after = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        _assert_equal(before, after, rtol=1e-12)
        _assert_equal(after, ref)
        assert sched.deadline(0) == 0.35
        assert sched.round_m_ud(0, 0, 0.0) == 5e5

    def test_lookups_use_normalised_arrays(self):
        sched = TimelineSchedule(n_rounds=2, deadline_s=0.7,
                                 m_ud_bits=[1e5, 2e5])
        assert sched.deadline(1) == 0.7
        assert sched.round_m_ud(1, 3, 0.0) == 2e5
        assert isinstance(sched.deadline_s, np.ndarray)
        assert isinstance(sched.m_ud_bits, np.ndarray)


class TestRoundViewInvariant:
    """Satellite bugfix: a missing round result with pending clients
    must raise instead of silently dropping their bits."""

    def test_none_result_with_pending_raises(self):
        with pytest.raises(RuntimeError, match="pending"):
            _round_view(2, 0.0, None, {7: 1e6}, 0.0)

    def test_none_result_without_pending_is_empty_round(self):
        rnd, carry = _round_view(2, 1.0, None, {}, 0.25)
        assert carry == {}
        assert rnd.sync_time == 0.25
        assert rnd.ul_bits == {} and rnd.arrived == []


class TestCoSimCoupled:
    def _cosim(self):
        pytest.importorskip("jax")
        import jax

        from repro.data import build_federated_cnn_clients
        from repro.fl import CPSServer, SelectionConfig
        from repro.fl.client import LocalTrainConfig
        from repro.fl.simulation import CoSimConfig, FLNetworkCoSim
        from repro.models import cnn

        clients, _ = build_federated_cnn_clients(
            n_clients=4, samples_per_client=16, loss_fn=cnn.loss_fn,
            train_cfg=LocalTrainConfig(lr=0.05, batch_size=8,
                                       local_epochs=1),
            seed=0,
        )
        server = CPSServer(
            global_params=cnn.init_params(jax.random.PRNGKey(0)),
            clients=clients,
            selection=SelectionConfig(strategy="all"),
            seed=0,
        )
        cfg = CoSimConfig(
            policy="bs", total_load=0.5, model_bits=2e6,
            upload_bits=2e6, timing_seeds=1,
            pon=PONConfig(n_onus=8, line_rate_bps=1e9),
        )
        return FLNetworkCoSim(server, cfg)

    def test_async_mode_runs_and_sums(self):
        sim = self._cosim()
        res = sim.run(n_rounds=3, mode="async", async_buffer=2)
        assert len(res.rounds) == 3
        assert all(r["n_arrived"] >= 1 for r in res.rounds)
        assert res.total_time_s == pytest.approx(
            sum(r["sync_time_s"] for r in res.rounds)
        )

    @pytest.mark.parametrize("dpolicy", ["defer", "drop", "partial"])
    def test_deadline_policies_run(self, dpolicy):
        sim = self._cosim()
        res = sim.run(n_rounds=2, deadline_s=2.0,
                      deadline_policy=dpolicy)
        assert len(res.rounds) == 2
        assert all(r["sync_time_s"] > 0 for r in res.rounds)

    def test_coupled_requires_single_timing_seed(self):
        """Arrival sets are events, not averageable times — multi-seed
        configs must be rejected, not silently collapsed to seed 0."""
        sim = self._cosim()
        sim.cfg.timing_seeds = 3
        with pytest.raises(ValueError, match="timing_seeds"):
            sim.run(n_rounds=1, mode="async", async_buffer=1)

    def test_failure_prob_drops_updates_in_coupled_path(self):
        """``failure_prob`` must roll in the coupled path exactly as in
        run_round: with certain failure no update ever applies."""
        import jax

        sim = self._cosim()
        sim.server.failure_prob = 1.0
        before = jax.tree.leaves(sim.server.global_params)[0].copy()
        res = sim.run(n_rounds=2, mode="async", async_buffer=2)
        after = jax.tree.leaves(sim.server.global_params)[0]
        assert all(r["n_arrived"] == 0 for r in res.rounds)
        np.testing.assert_array_equal(np.asarray(before),
                                      np.asarray(after))

    def test_async_rejects_compression_measured_bits(self):
        sim = self._cosim()
        with pytest.raises(ValueError, match="decoupled"):
            sim.run(n_rounds=1, mode="async", async_buffer=1,
                    update_bits_from_compression=True)

    def test_unknown_mode_raises(self):
        sim = self._cosim()
        with pytest.raises(ValueError, match="unknown mode"):
            sim.run(n_rounds=1, mode="eventually")


class TestCoSimTimingCacheKey:
    """Satellite bugfix: ``_round_sync_time`` must key on the payload
    sizes — mutating ``cfg`` between ``run()`` calls on a reused co-sim
    must re-simulate, not serve stale timings."""

    def test_model_bits_change_invalidates_cache(self):
        pytest.importorskip("jax")
        sim = TestCoSimCoupled._cosim(self)
        res1 = sim.run(n_rounds=1, backend="per_round")
        t1 = res1.rounds[0]["sync_time_s"]
        # mutate ONLY model_bits: the upload profiles (and with them
        # the old, buggy cache key) stay identical, but the download
        # broadcast grows ~0.9s — a stale cache would return t1
        sim.cfg.model_bits = sim.cfg.model_bits * 400
        res2 = sim.run(n_rounds=1, backend="per_round")
        t2 = res2.rounds[0]["sync_time_s"]
        assert t2 > t1 + 0.5, (
            "bigger model broadcast must yield a longer simulated "
            "sync (stale cache served)"
        )
