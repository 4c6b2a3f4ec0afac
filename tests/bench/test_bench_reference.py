"""The benchmark's plain reference against the program, on the CPU.

The reference (``bench/reference.py``) decides ``correct`` on the chip,
so here it must agree with the program's own numpy engine to the last
bit at small sizes, its copied traffic stream must be the program's
stream, and its float32 control must fail the limit that float64 meets.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import reference, streams  # noqa: E402
from bench.kinds import cosim  # noqa: E402

PON = dict(n_onus=16, line_rate_bps=10e9, distance_m=20_000.0,
           cycle_time_s=1e-3, efficiency=0.92, bg_burst_packets=16.0)
M_BITS = 2e6


def _clients(n, seed, t_hi=0.3):
    t_ud = np.random.default_rng(seed).uniform(0.05, t_hi, n)
    return [(i, float(t_ud[i]), M_BITS, 20_000.0) for i in range(n)]


def _program(clients, load, policy, seed, n_pons=1, cps=None, rnd=0):
    from repro.core.slicing import ClientProfile
    from repro.net import (FLRoundWorkload, MultiPonTopology, PONConfig,
                           SweepCase, SweepSpec, simulate)

    wl = FLRoundWorkload(
        clients=[ClientProfile(client_id=c[0], t_ud=c[1], t_dl=0.0,
                               m_ud_bits=c[2]) for c in clients],
        model_bits=M_BITS)
    topo = (MultiPonTopology(n_pons=n_pons, cps_rate_bps=cps)
            if n_pons > 1 else None)
    case = SweepCase(workload=wl, load=load, policy=policy, seed=seed,
                     stream_round=rnd, topology=topo)
    return simulate(SweepSpec(cases=(case,), pon=PONConfig(**PON)))[0]


def _reference(clients, load, policy, seed, n_pons=1, cps=None, rnd=0,
               dtype=np.float64):
    r = dict(clients=clients, model_bits=M_BITS, t_aggregate=0.0,
             load=load, policy=policy, seed=seed, stream_round=rnd)
    return reference.simulate_rounds(
        PON, dict(n_pons=n_pons, cps_rate_bps=cps), [r], dtype=dtype)[0]


def _as_round(res):
    return dict(dl_done=res.dl_done, ready=res.ready, ul_done=res.ul_done,
                sync_time=res.sync_time, ul_remaining=res.ul_remaining)


@pytest.mark.parametrize("policy,load,per_onu,n_pons,cps,rnd", [
    ("fcfs", 0.8, 1, 1, None, 0),
    ("fcfs", 0.3, 1, 1, None, 2),
    ("bs", 0.8, 1, 1, None, 0),
    ("fcfs", 0.9, 2, 1, None, 0),
    ("fcfs", 0.8, 1, 3, 0.9 * 3 * 10e9, 1),
    ("bs", 0.8, 1, 3, 0.5 * 3 * 10e9, 0),
])
def test_reference_matches_numpy_engine(policy, load, per_onu, n_pons, cps,
                                        rnd):
    clients = _clients(PON["n_onus"] * n_pons * per_onu, seed=11)
    got = _as_round(_program(clients, load, policy, 5, n_pons, cps, rnd))
    ref = _reference(clients, load, policy, 5, n_pons, cps, rnd)
    assert cosim.compare(got, ref) == (0.0, 0)


def test_stream_is_the_programs_stream():
    from repro.kernels.traffic.ops import make_stream_key, sample_arrival_bits

    key = streams.stream_key(123456789, 1, 3, 2)
    assert (key == make_stream_key(123456789, 1, 3, 2)).all()
    lam = streams.burst_lambda(60e6, 1e-3, 16.0)
    ours = streams.arrival_packets(key, lam, 128, 256, 32, 16.0)
    theirs = sample_arrival_bits(key, 128, 256, 32, lam, 1 / 16.0,
                                 streams.PACKET_BITS, backend="numpy")[0]
    assert ours.sum() > 0
    assert (ours * streams.PACKET_BITS == theirs).all()


@pytest.mark.parametrize("policy", ["fcfs", "bs"])
def test_float32_control_fails_the_limit(policy):
    with open(os.path.join(ROOT, "bench", "limits", "cosim.json")) as f:
        limit = json.load(f)["worst_rel_gap"]
    clients = _clients(PON["n_onus"], seed=3, t_hi=2.0)
    ref = _reference(clients, 0.8, policy, 9)
    ctl = _reference(clients, 0.8, policy, 9, dtype=np.float32)
    worst, bad = cosim.compare(ctl, ref)
    assert bad == 0
    assert worst > 3 * limit
