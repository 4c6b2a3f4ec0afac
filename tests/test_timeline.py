"""Multi-round timeline engine vs the per-round reference loop.

The timeline engine (folded and sequential modes) must reproduce the
cycle-by-cycle dict simulator driven one round at a time — same sync
times and same per-round served bits at rtol 1e-6 — including elastic
membership masks and deadline deferral, because both consume the
identical counter-keyed arrival streams.
"""
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
    simulate_timeline_reference,
)

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
    # fcfs exercises multi-client-per-ONU queues; bs needs ids < n_onus
    ids = range(6) if policy == "bs" else [0, 1, 5, 9, 17, 19]
    return FLRoundWorkload(clients=_clients(ids, seed), model_bits=1.5e6)


def _assert_equal(a, b, rtol=1e-6):
    for ra, rb in zip(a, b):
        assert np.allclose(ra.sync_times, rb.sync_times, rtol=rtol), (
            f"sync {ra.sync_times} vs {rb.sync_times}"
        )
        for x, y in zip(ra.rounds, rb.rounds):
            assert set(x.ul_bits) == set(y.ul_bits)
            for cid, bits in x.ul_bits.items():
                assert bits == pytest.approx(
                    y.ul_bits[cid], rel=rtol, abs=2.0
                ), f"round {x.round_index} client {cid}"
            assert set(x.deferred) == set(y.deferred)
            for cid, bits in x.deferred.items():
                assert bits == pytest.approx(y.deferred[cid], rel=rtol)
            assert x.arrived == y.arrived


class TestParityAgainstReference:
    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_elastic_membership(self, policy):
        rng = np.random.default_rng(17)
        memb = rng.random((3, 6)) < 0.7
        memb[0] = True
        sched = TimelineSchedule(n_rounds=3, membership=memb)
        cases = [SweepCase(workload=_wl(policy), load=0.5,
                           policy=policy, seed=3),
                 SweepCase(workload=_wl(policy), load=0.8,
                           policy=policy, seed=4)]
        _assert_equal(
            simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                               mode="folded")),
            simulate_timeline_reference(CFG, cases, sched),
        )

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_deadline_deferral(self, policy):
        sched = TimelineSchedule(n_rounds=4, deadline_s=0.35)
        cases = [SweepCase(workload=_wl(policy), load=0.6,
                           policy=policy, seed=5)]
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        assert sum(len(r.deferred) for r in eng[0].rounds) > 0, (
            "deadline chosen to force deferral"
        )
        _assert_equal(eng, ref)

    def test_folded_equals_sequential_exactly(self):
        rng = np.random.default_rng(2)
        memb = rng.random((4, 6)) < 0.6
        memb[0] = True
        sched = TimelineSchedule(n_rounds=4, membership=memb)
        for policy in ("fcfs", "bs"):
            cases = [SweepCase(workload=_wl(policy), load=0.7,
                               policy=policy, seed=1)]
            fold = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                      mode="folded"))
            seq = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                     mode="sequential"))
            _assert_equal(fold, seq, rtol=1e-12)


class TestMembershipDynamics:
    """Property: a client masked out of round r contributes no bits."""

    def test_masked_out_round_contributes_nothing(self):
        memb = np.ones((3, 6), bool)
        memb[1, 2] = False          # client at position 2 sits out r1
        sched = TimelineSchedule(n_rounds=3, membership=memb)
        wl = _wl("fcfs")
        skipped = wl.clients[2].client_id
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=0.5, policy="fcfs",
                             seed=0)],
            pon=CFG, schedule=sched,
        ))[0]
        assert res.rounds[1].ul_bits.get(skipped, 0.0) == 0.0
        assert skipped not in res.rounds[1].arrived
        # and participates normally around it
        assert res.rounds[0].ul_bits[skipped] > 0.0
        assert res.rounds[2].ul_bits[skipped] > 0.0

    def test_empty_round_costs_only_aggregation(self):
        memb = np.ones((3, 4), bool)
        memb[1] = False
        sched = TimelineSchedule(n_rounds=3, membership=memb)
        clients = _clients(range(4))
        wl = FLRoundWorkload(clients=clients, model_bits=1e6,
                             t_aggregate=0.25)
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=0.4, policy="fcfs",
                             seed=0)],
            pon=CFG, schedule=sched,
        ))[0]
        assert res.rounds[1].sync_time == 0.25
        assert res.rounds[1].ul_bits == {}


class TestDeadlineDynamics:
    """Property: a missed deadline defers — never drops — the
    remaining update bits to the next round."""

    def _run(self, policy="fcfs", deadline=0.3, rounds=5):
        sched = TimelineSchedule(n_rounds=rounds, deadline_s=deadline)
        wl = _wl(policy)
        return wl, simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=0.6, policy=policy,
                             seed=7)],
            pon=CFG, schedule=sched,
        ))[0]

    def test_deferred_bits_resume_next_round(self):
        wl, res = self._run()
        saw_deferral = False
        for r, nxt in zip(res.rounds, res.rounds[1:]):
            for cid, bits in r.deferred.items():
                saw_deferral = True
                assert bits > 0.0
                # the carrier's next-round service starts from exactly
                # the deferred bits (no re-download, no drop)
                nxt_served = nxt.ul_bits.get(cid, 0.0)
                nxt_left = nxt.deferred.get(cid, 0.0)
                assert nxt_served + nxt_left == pytest.approx(bits)
        assert saw_deferral

    def test_total_bits_conserved_per_upload(self):
        wl, res = self._run()
        m_ud = {c.client_id: c.m_ud_bits for c in wl.clients}
        served = {cid: 0.0 for cid in m_ud}
        uploads_done = {cid: 0 for cid in m_ud}
        for r in res.rounds:
            for cid, bits in r.ul_bits.items():
                served[cid] += bits
            for cid in r.arrived:
                uploads_done[cid] += 1
        for cid in m_ud:
            # every completed upload moved exactly m_ud bits; at most
            # one partial upload is still in flight at the horizon
            leftover = served[cid] - uploads_done[cid] * m_ud[cid]
            assert -2.0 <= leftover <= m_ud[cid]

    def test_sync_capped_by_deadline(self):
        _, res = self._run(deadline=0.3)
        for r in res.rounds:
            if r.deferred:
                assert r.sync_time == pytest.approx(0.3)

    def test_folded_mode_rejects_deadlines(self):
        sched = TimelineSchedule(n_rounds=2, deadline_s=0.5)
        with pytest.raises(ValueError, match="folded"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=_wl("fcfs"), load=0.5,
                                 policy="fcfs", seed=0)],
                pon=CFG, schedule=sched, mode="folded",
            ))


class TestScheduleValidation:
    def test_membership_width_checked(self):
        sched = TimelineSchedule(n_rounds=2,
                                 membership=np.ones((2, 3), bool))
        with pytest.raises(ValueError, match="membership"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=_wl("fcfs"), load=0.5,
                                 policy="fcfs", seed=0)],
                pon=CFG, schedule=sched,
            ))

    def test_membership_shape_checked(self):
        with pytest.raises(ValueError, match="membership"):
            TimelineSchedule(n_rounds=3,
                             membership=np.ones((2, 4), bool))

    def test_injected_arrivals_rejected(self):
        case = SweepCase(workload=_wl("fcfs"), load=0.5, policy="fcfs",
                         seed=0, dl_arrivals=np.zeros((10, 8)))
        with pytest.raises(ValueError, match="counter streams"):
            simulate(SweepSpec(cases=[case], pon=CFG,
                               schedule=TimelineSchedule(n_rounds=1)))

    def test_per_round_m_ud_override(self):
        sched = TimelineSchedule(
            n_rounds=2, m_ud_bits=np.array([4e5, 8e5])
        )
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=_wl("fcfs"), load=0.4, policy="fcfs",
                             seed=0)],
            pon=CFG, schedule=sched,
        ))[0]
        for r, expect in zip(res.rounds, (4e5, 8e5)):
            for bits in r.ul_bits.values():
                assert bits == pytest.approx(expect)


class TestCoSimBackend:
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
            upload_bits=2e6, timing_seeds=2,
            pon=PONConfig(n_onus=8, line_rate_bps=1e9),
        )
        return FLNetworkCoSim(server, cfg)

    def test_timeline_backend_is_default_and_complete(self):
        sim = self._cosim()
        res = sim.run(n_rounds=3)
        assert len(res.rounds) == 3
        syncs = [r["sync_time_s"] for r in res.rounds]
        assert all(s > 0 for s in syncs)
        assert res.total_time_s == pytest.approx(sum(syncs))
        assert res.sync_time_s == pytest.approx(syncs[-1])

    def test_per_round_backend_still_works(self):
        sim = self._cosim()
        res = sim.run(n_rounds=2, backend="per_round")
        assert len(res.rounds) == 2
        assert all(r["sync_time_s"] > 0 for r in res.rounds)

    def test_unknown_backend_raises(self):
        sim = self._cosim()
        with pytest.raises(ValueError, match="unknown backend"):
            sim.run(n_rounds=1, backend="magic")
