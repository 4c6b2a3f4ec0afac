"""Vectorized PON engine vs the cycle-by-cycle reference simulator.

The engine must reproduce the reference's per-client done-times exactly
(rtol 1e-6) when both consume the same background arrival process; the
property test drives both backends with identical injected arrival
matrices over random workloads, loads and policies.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # seeded trials below still cover parity
    HAVE_HYPOTHESIS = False

from repro.core.slicing import ClientProfile  # noqa: E402
from repro.net import (  # noqa: E402
    FLRoundWorkload,
    PONConfig,
    PrecomputedSource,
    SweepCase,
    SweepSpec,
    simulate,
    simulate_round_reference,
)

PACKET = 12_000.0            # 1500 B frames, as the traffic model


def _arrival_matrix(rng, n_cycles, n_onus, load, line_rate, cycle_s,
                    burst=8.0):
    per_onu = load * line_rate / n_onus
    lam = per_onu / (PACKET * burst) * cycle_s
    counts = rng.poisson(lam, (n_cycles, n_onus))
    packets = counts.astype(np.float64)
    nz = counts > 0
    if nz.any():
        packets[nz] += rng.negative_binomial(counts[nz], 1.0 / burst)
    return packets * PACKET


def _run_both(cfg, wl, policy, load, seed):
    T = 25_000
    rng = np.random.default_rng(seed + 10_000)
    dl = _arrival_matrix(rng, T, cfg.n_onus, load, cfg.line_rate_bps,
                         cfg.cycle_time_s)
    ul = _arrival_matrix(rng, T, cfg.n_onus, load, cfg.line_rate_bps,
                         cfg.cycle_time_s)
    ref = simulate_round_reference(
        cfg, wl, load, policy, seed=seed,
        _dl_sources=[PrecomputedSource(dl[:, i]) for i in range(cfg.n_onus)],
        _ul_sources=[PrecomputedSource(ul[:, i]) for i in range(cfg.n_onus)],
    )
    eng = simulate(SweepSpec(
        cases=[SweepCase(workload=wl, load=load, policy=policy, seed=seed,
                         dl_arrivals=dl, ul_arrivals=ul)],
        pon=cfg,
    ))[0]
    return ref, eng


def _assert_parity(ref, eng):
    for name in ("dl_done", "ready", "ul_done"):
        a, b = getattr(ref, name), getattr(eng, name)
        assert set(a) == set(b)
        for cid in a:
            assert b[cid] == pytest.approx(a[cid], rel=1e-6), (
                f"{name}[{cid}]: reference={a[cid]} vectorized={b[cid]}"
            )
    assert eng.sync_time == pytest.approx(ref.sync_time, rel=1e-6)
    assert eng.compute_bound == pytest.approx(ref.compute_bound, rel=1e-6)


class TestEngineMatchesReferenceSeeded:
    """Deterministic randomized parity trials (run with or without
    hypothesis installed)."""

    @pytest.mark.parametrize("trial", range(8))
    def test_parity_random_workloads(self, trial):
        rng = np.random.default_rng(1000 + trial)
        policy = ["fcfs", "bs"][trial % 2]
        n_onus = int(rng.integers(2, 6))
        n = int(rng.integers(1, 9))
        if policy == "bs":
            ids = rng.choice(n_onus, size=min(n, n_onus),
                             replace=False).tolist()
        else:
            # ids beyond n_onus exercise multi-client-per-ONU queues
            ids = list(dict.fromkeys(
                rng.integers(0, 3 * n_onus, size=n).tolist()
            ))
        clients = [
            ClientProfile(client_id=int(i),
                          t_ud=float(rng.uniform(0.05, 1.5)),
                          t_dl=0.0,
                          m_ud_bits=float(rng.uniform(1e4, 3e6)))
            for i in ids
        ]
        cfg = PONConfig(n_onus=n_onus, line_rate_bps=1e9)
        wl = FLRoundWorkload(clients=clients, model_bits=1.5e6)
        load = float(rng.uniform(0.05, 0.85))
        ref, eng = _run_both(cfg, wl, policy, load, seed=trial)
        _assert_parity(ref, eng)


if HAVE_HYPOTHESIS:
    workloads = st.lists(
        st.tuples(
            st.floats(0.05, 1.5),        # t_ud
            st.floats(1e4, 3e6),         # m_ud bits
        ),
        min_size=1,
        max_size=8,
    )

    class TestEngineMatchesReferenceHypothesis:
        @settings(max_examples=12, deadline=None)
        @given(workloads, st.floats(0.05, 0.85), st.integers(0, 10_000),
               st.integers(2, 5))
        def test_fcfs_parity_random_workloads(self, profs, load, seed,
                                              n_onus):
            # ids beyond n_onus exercise multi-client-per-ONU queues
            clients = [
                ClientProfile(client_id=3 * i + 1, t_ud=t, t_dl=0.0,
                              m_ud_bits=m)
                for i, (t, m) in enumerate(profs)
            ]
            cfg = PONConfig(n_onus=n_onus, line_rate_bps=1e9)
            wl = FLRoundWorkload(clients=clients, model_bits=1.5e6)
            ref, eng = _run_both(cfg, wl, "fcfs", load, seed)
            _assert_parity(ref, eng)

        @settings(max_examples=12, deadline=None)
        @given(workloads, st.floats(0.05, 0.85), st.integers(0, 10_000))
        def test_bs_parity_random_workloads(self, profs, load, seed):
            n_onus = max(len(profs), 2)
            clients = [
                ClientProfile(client_id=i, t_ud=t, t_dl=0.0, m_ud_bits=m)
                for i, (t, m) in enumerate(profs)
            ]
            cfg = PONConfig(n_onus=n_onus, line_rate_bps=1e9)
            wl = FLRoundWorkload(clients=clients, model_bits=1.5e6)
            ref, eng = _run_both(cfg, wl, "bs", load, seed)
            _assert_parity(ref, eng)


class TestSeedRegression:
    """The reference backend's sync_time at the paper's operating point
    (128 ONUs, 10G, 26.416 Mbit updates, load 0.8, seed 1) must stay
    exactly what the seed repo produced."""

    @staticmethod
    def _workload(n=12):
        rng = np.random.default_rng(42)
        t_uds = rng.uniform(1.0, 5.0, 128)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                          m_ud_bits=26.416e6)
            for i in range(n)
        ]
        return FLRoundWorkload(clients=clients, model_bits=26.416e6)

    def test_reference_fcfs_sync_unchanged_from_seed(self):
        r = simulate_round_reference(PONConfig(n_onus=128),
                                     self._workload(), 0.8, "fcfs", seed=1)
        assert r.sync_time == pytest.approx(5.029100000000014, abs=1e-9)

    def test_reference_bs_sync_unchanged_from_seed(self):
        r = simulate_round_reference(PONConfig(n_onus=128),
                                     self._workload(), 0.8, "bs", seed=1)
        assert r.sync_time == pytest.approx(4.909099999999974, abs=1e-9)

    def test_vectorized_close_to_reference_at_operating_point(self):
        # different RNG stream, same queueing model: close, not equal
        case = SweepCase(workload=self._workload(), load=0.8,
                         policy="fcfs", seed=1)
        r = simulate(SweepSpec(cases=(case,), pon=PONConfig(n_onus=128)))[0]
        assert r.sync_time == pytest.approx(5.0291, rel=0.05)


class TestSweepAPI:
    def _cases(self):
        rng = np.random.default_rng(3)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t), t_dl=0.0,
                          m_ud_bits=2e6)
            for i, t in enumerate(rng.uniform(0.2, 1.0, 6))
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=2e6)
        return [
            SweepCase(workload=wl, load=load, policy=policy, seed=s)
            for policy in ("fcfs", "bs")
            for load in (0.3, 0.8)
            for s in (0, 1)
        ]

    def test_batched_equals_per_case(self):
        """Batch composition must not change any case's result."""
        cfg = PONConfig(n_onus=8, line_rate_bps=1e9)
        cases = self._cases()
        batched = simulate(SweepSpec(cases=cases, pon=cfg))
        for case, got in zip(cases, batched):
            solo = simulate(SweepSpec(cases=[case], pon=cfg))[0]
            assert got.sync_time == solo.sync_time
            assert got.ul_done == solo.ul_done

    def test_sweep_preserves_headline_ordering(self):
        cfg = PONConfig(n_onus=8, line_rate_bps=1e9)
        res = {(c.policy, c.load, c.seed): r
               for c, r in zip(self._cases(),
                               simulate(SweepSpec(cases=self._cases(),
                                                  pon=cfg)))}
        # BS is load-independent; FCFS grows with load
        assert res[("bs", 0.8, 0)].sync_time == pytest.approx(
            res[("bs", 0.3, 0)].sync_time, rel=0.05
        )
        assert (res[("fcfs", 0.8, 0)].sync_time
                >= res[("fcfs", 0.3, 0)].sync_time - 1e-6)

    def test_bs_requires_client_ids_within_onus(self):
        clients = [ClientProfile(client_id=9, t_ud=0.5, t_dl=0.0,
                                 m_ud_bits=1e6)]
        wl = FLRoundWorkload(clients=clients, model_bits=1e6)
        with pytest.raises(ValueError, match="client_id < n_onus"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.5, policy="bs",
                                 seed=0)],
                pon=PONConfig(n_onus=4),
            ))

    def test_duplicate_client_ids_rejected(self):
        clients = [
            ClientProfile(client_id=1, t_ud=0.5, t_dl=0.0, m_ud_bits=1e6),
            ClientProfile(client_id=1, t_ud=0.7, t_dl=0.0, m_ud_bits=1e6),
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=1e6)
        with pytest.raises(ValueError, match="duplicate client_id"):
            simulate(SweepSpec(
                cases=[SweepCase(workload=wl, load=0.5, policy="fcfs",
                                 seed=0)],
                pon=PONConfig(n_onus=4),
            ))


class TestBgQueueSnapHead:
    """A partial grant whose sub-1-bit residue snaps a segment away
    must leave the head pointer on the next *arrival* cycle (the
    reference's restore loop), not on a possibly-empty ptr+1 cycle."""

    def test_snap_advances_to_next_real_segment(self):
        from repro.net.dba import OnuQueue
        from repro.net.engine import _BgQueues

        bg = _BgQueues(1, 1)
        ref = OnuQueue(0)
        arrivals = [1000.0, 0.0, 0.0, 500.0]
        for k, bits in enumerate(arrivals):
            bg.push(k, np.array([[bits]]))
            if bits:
                ref.push("bg", bits, float(k))
        bg.serve(np.array([[999.5]]), k=3)
        ref.serve(999.5, kind="bg")
        assert bg.backlog[0, 0] == pytest.approx(ref.backlog)
        # FCFS age key == the surviving segment's arrival cycle
        assert int(bg.hol_key()[0, 0]) == 3
        assert ref.hol_time == pytest.approx(3.0)


class TestServeRebuild:
    """The single-pass OnuQueue.serve keeps its exact semantics."""

    def test_many_segments_fifo_and_compaction(self):
        from repro.net.dba import OnuQueue

        q = OnuQueue(0)
        for i in range(50):
            q.push("bg", 100.0, t=float(i))
        served = q.serve(3 * 100.0 + 99.5)     # leaves 0.5 bit in seg 3
        assert served["bg"] == pytest.approx(399.5)
        # the 0.5-bit remnant is compacted away; 46 segments remain
        assert len(q.segments) == 46
        assert q.hol_time == pytest.approx(4.0)
        assert q.backlog == pytest.approx(46 * 100.0)

    def test_kind_filter_preserves_other_kind(self):
        from repro.net.dba import OnuQueue

        q = OnuQueue(0)
        q.push("bg", 50.0, t=0.0)
        q.push("fl", 80.0, t=1.0)
        q.push("bg", 50.0, t=2.0)
        served = q.serve(100.0, kind="bg")
        assert served == {"bg": pytest.approx(100.0)}
        assert q.backlog_of("fl") == pytest.approx(80.0)
        assert q.hol_time == pytest.approx(1.0)
