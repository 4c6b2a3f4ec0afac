"""Integration tests for the PON simulator: the paper's headline behaviour."""
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    OnuQueue,
    PONConfig,
    SweepCase,
    SweepSpec,
    simulate,
)
from repro.net.dba import FCFSBestEffort
from repro.net.traffic import PoissonSource, background_rate_for_load

M = 26.416e6


def mk_workload(n=32, seed=0):
    rng = np.random.default_rng(seed)
    clients = [
        ClientProfile(client_id=i, t_ud=float(t), t_dl=0.0, m_ud_bits=M)
        for i, t in enumerate(rng.uniform(1.0, 5.0, n))
    ]
    return FLRoundWorkload(clients=clients, model_bits=M)


def simulate_one(cfg, wl, load, policy, seed):
    """One round of the batched engine, through the public entry point."""
    case = SweepCase(workload=wl, load=load, policy=policy, seed=seed)
    return simulate(SweepSpec(cases=(case,), pon=cfg))[0]


class TestOnuQueue:
    def test_fifo_order_across_kinds(self):
        q = OnuQueue(0)
        q.push("bg", 100.0, t=0.0)
        q.push("fl", 50.0, t=1.0)
        served = q.serve(120.0)
        assert served["bg"] == pytest.approx(100.0)
        assert served["fl"] == pytest.approx(20.0)
        assert q.backlog == pytest.approx(30.0)

    def test_kind_filtered_service(self):
        q = OnuQueue(0)
        q.push("bg", 100.0, t=0.0)
        q.push("fl", 50.0, t=1.0)
        served = q.serve(60.0, kind="fl")
        assert served == {"fl": pytest.approx(50.0)}
        assert q.backlog_of("bg") == pytest.approx(100.0)


class TestDBA:
    def test_background_assured_first(self):
        dba = FCFSBestEffort(10e9, 1e-3, 4, efficiency=1.0)
        queues = [OnuQueue(i) for i in range(4)]
        queues[0].push("bg", 6e6, 0.0)
        queues[1].push("fl", 9e6, 0.0)
        grants = dba.grant(queues)
        assert grants[0]["bg"] == pytest.approx(6e6)
        # residual 4e6 goes to the FL queue
        assert grants[1]["fl"] == pytest.approx(4e6)

    def test_fl_fcfs_order_by_hol_age(self):
        dba = FCFSBestEffort(10e9, 1e-3, 4, efficiency=1.0)
        queues = [OnuQueue(i) for i in range(3)]
        queues[0].push("fl", 8e6, t=2.0)
        queues[1].push("fl", 8e6, t=1.0)     # older -> served first
        grants = dba.grant(queues)
        assert grants[1]["fl"] == pytest.approx(8e6)
        assert grants[0]["fl"] == pytest.approx(2e6)


class TestRoundSimulation:
    def test_bs_sync_time_independent_of_load(self):
        cfg = PONConfig(n_onus=32)
        wl = mk_workload(32)
        r_low = simulate_one(cfg, wl, 0.3, "bs", seed=1)
        r_high = simulate_one(cfg, wl, 0.8, "bs", seed=1)
        assert r_high.sync_time == pytest.approx(r_low.sync_time, rel=0.05)

    def test_fcfs_sync_grows_with_load(self):
        cfg = PONConfig(n_onus=32)
        wl = mk_workload(32)
        r_low = simulate_one(cfg, wl, 0.3, "fcfs", seed=1)
        r_high = simulate_one(cfg, wl, 0.85, "fcfs", seed=1)
        assert r_high.sync_time > r_low.sync_time

    def test_bs_beats_fcfs_at_high_load(self):
        cfg = PONConfig(n_onus=32)
        wl = mk_workload(32)
        r_bs = simulate_one(cfg, wl, 0.8, "bs", seed=1)
        r_fcfs = simulate_one(cfg, wl, 0.8, "fcfs", seed=1)
        assert r_bs.sync_time < r_fcfs.sync_time

    def test_bs_sync_pinned_near_compute_bound(self):
        cfg = PONConfig(n_onus=32)
        wl = mk_workload(32)
        r = simulate_one(cfg, wl, 0.8, "bs", seed=1)
        # comm overhead = slice drain, a small fraction of the round
        assert r.comm_overhead < 0.25 * r.sync_time

    def test_all_uploads_complete(self):
        cfg = PONConfig(n_onus=16)
        wl = mk_workload(16)
        for policy in ("fcfs", "bs"):
            r = simulate_one(cfg, wl, 0.5, policy, seed=2)
            assert len(r.ul_done) == 16
            assert r.sync_time < 60.0


class TestTraffic:
    def test_poisson_rate_converges(self):
        rng = np.random.default_rng(0)
        src = PoissonSource(rate_bps=1e9, rng=rng, burst_packets=8.0)
        total = sum(src.arrivals(1e-3) for _ in range(20000))
        assert total / 20.0 == pytest.approx(1e9, rel=0.1)

    def test_background_rate_subtracts_training(self):
        assert background_rate_for_load(0.8, 10e9, 1e9) == pytest.approx(7e9)
        assert background_rate_for_load(0.05, 10e9, 1e9) == 0.0
