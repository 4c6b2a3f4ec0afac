"""Multi-PON stacked engine vs the per-PON reference oracle.

The wavelength-stacked engine (``(case, pon)`` rows + per-cycle CPS
waterfill, ``repro.net.engine``) must reproduce the cycle-by-cycle
per-PON dict simulator with the CPS post-pass
(``repro.net.multi_pon.simulate_multi_pon_round``) at rtol 1e-6 —
both DBA policies, shared-ONU clients, elastic membership and deadline
deferral — because both consume the identical counter streams keyed
``(seed, phase, round, pon)``.  The waterfill itself is
property-tested (conservation, bounds, per-PON monotonicity), and the
``n_pons=1`` path is pinned bitwise against the PR 3 stream and engine
values.
"""
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.kernels.traffic import ops
from repro.net import (
    FLRoundWorkload,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    cps_waterfill,
    simulate,
    simulate_multi_pon_round,
    simulate_timeline_reference,
)

CFG = PONConfig(n_onus=4, line_rate_bps=1e9)


def _clients(ids, seed=0, m_lo=1e5, m_hi=1e6):
    rng = np.random.default_rng(seed)
    return [
        ClientProfile(client_id=int(i),
                      t_ud=float(rng.uniform(0.05, 0.5)), t_dl=0.0,
                      m_ud_bits=float(rng.uniform(m_lo, m_hi)))
        for i in ids
    ]


def _assert_round_parity(ref, eng, rtol=1e-6):
    for name in ("dl_done", "ready", "ul_done"):
        a, b = getattr(ref, name), getattr(eng, name)
        assert set(a) == set(b)
        for cid in a:
            if np.isnan(a[cid]):
                assert np.isnan(b[cid])
                continue
            assert b[cid] == pytest.approx(a[cid], rel=rtol, abs=1e-12), (
                f"{name}[{cid}]: oracle={a[cid]} engine={b[cid]}"
            )
    assert eng.sync_time == pytest.approx(ref.sync_time, rel=rtol)
    assert eng.compute_bound == pytest.approx(ref.compute_bound, rel=rtol)


class TestCpsWaterfill:
    def test_unconstrained_is_identity(self):
        want = np.array([[2.0, 3.0, 1.0]])
        assert np.array_equal(cps_waterfill(want, 10.0), want)

    def test_conservation_bounds_and_level(self):
        rng = np.random.default_rng(0)
        for _ in range(50):
            P = int(rng.integers(2, 9))
            want = rng.uniform(0.0, 10.0, (4, P))
            cap = float(rng.uniform(1.0, 0.9 * want.sum(axis=1).max()))
            eff = cps_waterfill(want, cap)
            assert (eff >= 0.0).all()
            assert (eff <= want + 1e-12).all()
            # served never exceeds the CPS capacity per cycle
            assert (eff.sum(axis=1) <= cap * (1 + 1e-12) + 1e-9).all()
            for g in range(want.shape[0]):
                if want[g].sum() <= cap:
                    assert np.array_equal(eff[g], want[g])
                else:
                    # binding rows sit at one water level: every PON cut
                    # below its demand gets the same share mu
                    assert eff[g].sum() == pytest.approx(cap, rel=1e-12)
                    cut = eff[g] < want[g] - 1e-9
                    assert cut.any()
                    assert np.ptp(eff[g][cut]) <= 1e-9 * max(cap, 1.0)

    def test_monotone_in_capacity(self):
        rng = np.random.default_rng(1)
        want = rng.uniform(0.0, 5.0, (3, 6))
        prev = np.zeros_like(want)
        for cap in np.linspace(0.5, want.sum(axis=1).max() + 1, 40):
            eff = cps_waterfill(want, float(cap))
            assert (eff >= prev - 1e-9).all(), "per-PON grant decreased"
            prev = eff

    def test_batched_matches_per_row(self):
        rng = np.random.default_rng(2)
        want = rng.uniform(0.0, 4.0, (8, 5))
        caps = rng.uniform(2.0, 12.0, 8)
        batched = np.stack([
            cps_waterfill(want[g], float(caps[g])) for g in range(8)
        ])
        got = np.stack([
            cps_waterfill(want[g:g + 1], float(caps[g]))[0]
            for g in range(8)
        ])
        assert np.array_equal(batched, got)


class TestEngineMatchesOracle:
    """Seeded randomized parity trials (dict-sim oracle, so kept small)."""

    @pytest.mark.parametrize("trial", range(8))
    def test_parity_random_workloads(self, trial):
        rng = np.random.default_rng(500 + trial)
        policy = ["fcfs", "bs"][trial % 2]
        P = int(rng.integers(2, 4))
        n_local = int(rng.integers(2, 5))
        cfg = PONConfig(n_onus=n_local, line_rate_bps=1e9)
        total = P * n_local
        # every other trial contends on the CPS (stable offered load,
        # bursty demand exceeding the CPS in plenty of cycles)
        cps = None if trial % 4 < 2 else 0.55e9 * P
        topo = MultiPonTopology(n_pons=P, cps_rate_bps=cps)
        n = int(rng.integers(2, 7))
        if policy == "bs":
            ids = rng.choice(total, size=min(n, total),
                             replace=False).tolist()
        else:
            # ids beyond total exercise shared-ONU (multi-client) queues
            ids = list(dict.fromkeys(
                rng.integers(0, 3 * total, size=n).tolist()
            ))
        wl = FLRoundWorkload(clients=_clients(ids, seed=trial),
                             model_bits=1.2e6)
        load = float(rng.uniform(0.1, 0.4))
        eng = simulate(SweepSpec(
            cases=[SweepCase(workload=wl, load=load, policy=policy,
                             seed=trial, topology=topo)],
            pon=cfg,
        ))[0]
        ref = simulate_multi_pon_round(
            cfg, topo, wl, load, policy, seed=trial
        )
        _assert_round_parity(ref, eng)

    def test_batched_cases_match_solo(self):
        """Batch composition must not change a multi-PON case."""
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=1.1e9)
        wl = FLRoundWorkload(clients=_clients([0, 1, 5, 6, 7], seed=5),
                             model_bits=1e6)
        cases = [
            SweepCase(workload=wl, load=load, policy=policy, seed=s,
                      topology=topo)
            for policy in ("fcfs", "bs") for load in (0.2, 0.35)
            for s in (0, 1)
        ]
        batched = simulate(SweepSpec(cases=cases, pon=CFG))
        for case, got in zip(cases, batched):
            solo = simulate(SweepSpec(cases=[case], pon=CFG))[0]
            assert got.sync_time == solo.sync_time
            assert got.ul_done == solo.ul_done

    def test_per_pon_rate_overrides(self):
        """A slower wavelength stretches its own clients' times only."""
        topo_eq = MultiPonTopology(n_pons=2)
        topo_slow = MultiPonTopology(n_pons=2,
                                     pon_rates_bps=(1e9, 0.25e9))
        wl = FLRoundWorkload(
            clients=_clients([0, 5], seed=2, m_lo=2e7, m_hi=2e7),
            model_bits=2e6,
        )
        eng = {
            name: simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.2, policy="fcfs",
                                 seed=0, topology=t)],
                pon=CFG,
            ))[0]
            for name, t in (("eq", topo_eq), ("slow", topo_slow))
        }
        ref = simulate_multi_pon_round(CFG, topo_slow, wl, 0.2, "fcfs",
                                       seed=0)
        _assert_round_parity(ref, eng["slow"])
        # client 5 sits on PON 1 (the throttled wavelength): its upload
        # service time stretches ~4x while client 0's stays put
        slow5 = eng["slow"].ul_done[5] - eng["slow"].ready[5]
        eq5 = eng["eq"].ul_done[5] - eng["eq"].ready[5]
        assert slow5 > 2.0 * eq5
        assert eng["slow"].ul_done[0] - eng["slow"].ready[0] == (
            pytest.approx(eng["eq"].ul_done[0] - eng["eq"].ready[0],
                          rel=0.25)
        )

    def test_tighter_cps_never_speeds_up(self):
        wl = FLRoundWorkload(clients=_clients([0, 1, 5, 6], seed=3),
                             model_bits=1.5e6)
        syncs = []
        for cps in (None, 2.0e9, 1.5e9, 1.05e9):
            topo = MultiPonTopology(n_pons=2, cps_rate_bps=cps)
            syncs.append(simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.35, policy="fcfs",
                                 seed=1, topology=topo)],
                pon=CFG,
            ))[0].sync_time)
        assert all(b >= a - 1e-9 for a, b in zip(syncs, syncs[1:])), syncs


class TestTimelineMultiPon:
    TOPO = MultiPonTopology(n_pons=2, cps_rate_bps=1.1e9)

    def _wl(self, policy, seed=0):
        ids = range(6) if policy == "bs" else [0, 1, 5, 9, 13]
        return FLRoundWorkload(clients=_clients(ids, seed),
                               model_bits=1e6)

    def _assert_equal(self, a, b, rtol=1e-6):
        for ra, rb in zip(a, b):
            assert np.allclose(ra.sync_times, rb.sync_times, rtol=rtol)
            for x, y in zip(ra.rounds, rb.rounds):
                assert set(x.ul_bits) == set(y.ul_bits)
                for cid, bits in x.ul_bits.items():
                    assert bits == pytest.approx(y.ul_bits[cid],
                                                 rel=rtol, abs=2.0)
                assert set(x.deferred) == set(y.deferred)
                assert x.arrived == y.arrived

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_elastic_membership_parity(self, policy):
        rng = np.random.default_rng(11)
        memb = rng.random((3, 5 if policy == "fcfs" else 6)) < 0.7
        memb[0] = True
        sched = TimelineSchedule(n_rounds=3, membership=memb)
        cases = [SweepCase(workload=self._wl(policy), load=0.3,
                           policy=policy, seed=7, topology=self.TOPO)]
        self._assert_equal(
            simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                               mode="folded")),
            simulate_timeline_reference(CFG, cases, sched),
        )

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_deadline_deferral_parity(self, policy):
        sched = TimelineSchedule(n_rounds=3, deadline_s=0.25)
        cases = [SweepCase(workload=self._wl(policy), load=0.3,
                           policy=policy, seed=9, topology=self.TOPO)]
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        assert sum(len(r.deferred) for r in eng[0].rounds) > 0
        self._assert_equal(eng, ref)


class TestStreamPinning:
    """The (seed, phase, round, pon) key leaves pon=0 streams bitwise
    where PR 3 pinned them."""

    def test_pon0_key_is_pr3_key(self):
        for seed, phase, rnd in [(0, 0, 0), (3, 1, 2), (77, 0, 9)]:
            legacy = np.array(
                [seed & 0xFFFFFFFF, (phase + 2 * rnd) & 0xFFFFFFFF],
                np.uint32,
            )
            assert np.array_equal(
                ops.make_stream_key(seed, phase, rnd), legacy
            )
            assert np.array_equal(
                ops.make_stream_key(seed, phase, rnd, pon=0), legacy
            )

    def test_pon_keys_distinct(self):
        keys = {tuple(ops.make_stream_key(3, 1, 2, pon=p).tolist())
                for p in range(64)}
        assert len(keys) == 64

    def test_pon_axis_fingerprint_pinned(self):
        """Pins the pon>0 stream definition itself (key mixing plus the
        sampler). Update deliberately if the stream format changes."""
        key = ops.make_stream_key(seed=3, phase=1, round_index=2, pon=1)
        assert key.tolist() == [3432918356, 461845912]
        got = ops.sample_arrival_bits(key, 128, 256, 8, 0.5, 1 / 16.0,
                                      12_000.0, backend="numpy")
        assert got.sum() == 193_656_000.0
        assert got[0, :7, 0].tolist() == [
            72000.0, 0.0, 24000.0, 0.0, 0.0, 0.0, 0.0
        ]

    def test_single_pon_engine_bitwise_unchanged(self):
        """n_pons=1 must reproduce the PR 3 engine exactly: the pinned
        Fig. 2b operating-point sync (BENCH_net_engine.json) with and
        without a trivial topology attached."""
        rng = np.random.default_rng(42)
        t_uds = rng.uniform(1.0, 5.0, 128)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                          m_ud_bits=26.416e6)
            for i in range(12)
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=26.416e6)
        cfg = PONConfig(n_onus=128)
        for topo in (None, MultiPonTopology()):
            r = simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.8, policy="fcfs",
                                 seed=1, topology=topo)],
                pon=cfg,
            ))[0]
            assert r.sync_time == pytest.approx(5.058100000000024,
                                                abs=1e-9)


class TestTopologyValidation:
    def test_mixed_topologies_rejected(self):
        wl = FLRoundWorkload(clients=_clients([0, 1]), model_bits=1e6)
        cases = [
            SweepCase(workload=wl, load=0.3, policy="fcfs", seed=0,
                      topology=MultiPonTopology(n_pons=2)),
            SweepCase(workload=wl, load=0.3, policy="fcfs", seed=0,
                      topology=MultiPonTopology(n_pons=3)),
        ]
        with pytest.raises(ValueError, match="share one"):
            simulate(SweepSpec(cases=cases, pon=CFG))

    def test_bs_ids_must_fit_the_stack(self):
        wl = FLRoundWorkload(clients=_clients([9]), model_bits=1e6)
        with pytest.raises(ValueError, match="client_id < n_onus"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.3, policy="bs", seed=0,
                                 topology=MultiPonTopology(n_pons=2))],
                pon=CFG,
            ))

    def test_pon_rates_length_checked(self):
        with pytest.raises(ValueError, match="pon_rates_bps"):
            MultiPonTopology(n_pons=2, pon_rates_bps=(1e9,))

    def test_cps_rate_positive(self):
        with pytest.raises(ValueError, match="cps_rate_bps"):
            MultiPonTopology(n_pons=2, cps_rate_bps=0.0)
