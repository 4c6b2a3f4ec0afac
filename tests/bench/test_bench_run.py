"""A whole run of a small cell on the CPU, with the chip check skipped:
``correct`` holds for the program as it is, and comes out false for
each fault the co-simulator cells can have, planted underneath the
timed path."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CONFIG = {
    "kind": "cosim",
    "pon": {"n_onus": 8, "line_rate_bps": 10e9, "distance_m": 20_000.0,
            "cycle_time_s": 1e-3, "efficiency": 0.92,
            "bg_burst_packets": 16.0},
    "topology": {"n_pons": 1, "cps_rate_bps": None},
    "clients": {"m_ud_bits": 2e6, "model_bits": 2e6, "t_ud_s": [0.05, 0.3],
                "t_aggregate_s": 0.0},
    "t_round_hint_s": 10.0, "max_t_s": 600.0, "chips": 1,
}
GRID = {"policies": ["fcfs", "bs"], "loads": [0.8], "seeds_per_point": 2,
        "clients_per_onu": 1, "rank_seed": 5,
        "check": [{"policy": "fcfs"}, {"policy": "bs"}]}
TIMELINE = {"policies": ["fcfs", "bs"], "loads": [0.8],
            "seeds_per_point": 1, "clients_per_onu": 1, "rank_seed": 5,
            "n_rounds": 3,
            "mode": "folded", "check": [{"policy": "fcfs"},
                                        {"policy": "bs"}]}


def _cell(traffic, config=CONFIG):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return dict(cell={"name": "tiny", "chips": 1}, config=config,
                traffic=traffic, end_to_end=bench["end_to_end"],
                per_layer=[])


def _run(capsys, cell, seed=2**31 + 11, control=False):
    import jax

    rc = harness.execute(ROOT, cell, jax.devices()[:1], seed, 1e-3, False,
                         0.0, control=control)
    out = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert "check worst_rel_gap" in out.err.strip().splitlines()[-2]
    return line


TWO_PER_ONU = {"policies": ["fcfs"], "loads": [0.3, 0.9],
               "seeds_per_point": 1, "clients_per_onu": 2, "rank_seed": 5,
               "check": [{"load": 0.9}, {"load": 0.3}]}
# a mix whose clients fill only some ONUs, named by count
FEW_CLIENTS = {"policies": ["fcfs", "bs"], "loads": [0.8],
               "seeds_per_point": 1, "n_clients": 3, "rank_seed": 5,
               "check": [{"policy": "fcfs"}, {"policy": "bs"}]}


@pytest.mark.parametrize("traffic", [GRID, TWO_PER_ONU, FEW_CLIENTS],
                         ids=["grid", "two_per_onu", "few_clients"])
def test_sound_run_is_correct(capsys, traffic):
    line = _run(capsys, _cell(traffic))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"setup_s", "sim_rounds_per_s"}
    assert line["metrics"]["sim_rounds_per_s"]["value"] > 0


def test_sound_stacked_timeline_is_correct(capsys):
    config = dict(CONFIG, topology={"n_pons": 2, "cps_rate_bps": 1.8e10})
    line = _run(capsys, _cell(TIMELINE, config), seed=4)
    assert line["correct"] is True, line["checks"]


def _shift_first_client(out, cyc=1e-3):
    done, rem = out
    done = np.array(done)
    done[0, 0] += cyc
    return done, rem


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_batch"])
def test_broken_path_is_not_correct(capsys, monkeypatch, fault):
    import repro.net
    from repro.kernels.ponsim import ops

    real_phase = ops.run_phase_device
    real_simulate = repro.net.simulate
    if fault == "answer_altered":
        monkeypatch.setattr(ops, "run_phase_device", lambda *a, **k: (
            _shift_first_client(real_phase(*a, **k))))
    elif fault == "state_unchanged":
        def unchanged(cfg, lay, rem_init, ready_t, mode, **kw):
            return np.full(rem_init.shape, np.nan), np.array(rem_init)

        monkeypatch.setattr(ops, "run_phase_device", unchanged)
    else:
        monkeypatch.setattr(repro.net, "simulate", lambda spec: (
            real_simulate(spec)[:len(spec.cases) // 2]))
    line = _run(capsys, _cell(GRID))
    assert line["correct"] is False
    assert line["failed"] > 0


@pytest.mark.parametrize("traffic", [GRID, TIMELINE],
                         ids=["grid", "timeline"])
def test_float32_control_in_the_programs_place_is_not_correct(capsys,
                                                              traffic):
    # rounds of some seconds, as in the cells, where float32's clock
    # drifts past the limit
    config = dict(CONFIG, clients=dict(CONFIG["clients"], t_ud_s=[1.0, 2.0]))
    if "n_rounds" in traffic:
        config["topology"] = {"n_pons": 2, "cps_rate_bps": 1.8e10}
    line = _run(capsys, _cell(traffic, config), seed=4, control=True)
    assert line["correct"] is False
    assert line["checks"]["mismatches"]["value"] == 0
    gap = line["checks"]["worst_rel_gap"]
    assert gap["value"] > 3 * gap["limit"]
