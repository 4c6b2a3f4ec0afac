"""The benchmark's own arithmetic and plumbing, on the CPU.

Cells, deployments, traffic mixes and metric readers are found by name;
rates are taken over whole calls; a run refuses a device that is not a
TPU; and a whole run, with the chip check skipped, reports ``correct``
for the program as it is and not for the program broken underneath.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace  # noqa: E402
from bench.kinds import cosim  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_found_by_name(name):
    cell = harness.load_cell(ROOT, name)
    assert cell["config"]["name"] == cell["cell"]["config"]
    assert cell["config"]["chips"] == cell["cell"]["chips"]
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    kind = harness.load_kind(cell["config"]["kind"])
    assert kind.RATE in {m["name"] for m in cell["end_to_end"]}
    assert issubclass(kind.Control, kind.Cell)
    assert set(harness.limits(cell["config"]["kind"]))
    sim = kind.Cell(cell["config"], cell["traffic"], seed=2**31 + 5)
    call = sim.call(3)
    assert len(call["cases"]) == sim.n_cases
    assert len(call["clients"]) == sim.n_clients
    assert call == sim.call(3)                 # the same seed, the same call
    assert call != sim.call(4)
    assert sim.sample([call], np.random.default_rng(0))


@pytest.mark.parametrize("name", sorted({w["traffic"]
                                         for w in BENCH["workloads"]}))
def test_compute_times_are_stratified_with_fixed_ranks(name):
    """Every seed gives one compute time in each of the n equal strata
    of the configured range, with the stratum of each client fixed by
    the mix, and fresh values and stream seeds."""
    cell = next(harness.load_cell(ROOT, w["name"])
                for w in BENCH["workloads"] if w["traffic"] == name)
    lo, hi = cell["config"]["clients"]["t_ud_s"]
    calls = [cosim.Cell(cell["config"], cell["traffic"], seed).call(1)
             for seed in (3, 2**31 + 7)]
    strata = []
    for call in calls:
        t = np.array([c[1] for c in call["clients"]])
        k = np.floor((t - lo) / (hi - lo) * len(t)).astype(int)
        assert sorted(k) == list(range(len(t)))
        strata.append(k)
    assert (strata[0] == strata[1]).all()
    assert calls[0]["clients"] != calls[1]["clients"]
    assert calls[0]["cases"] != calls[1]["cases"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell(ROOT, "no-such-cell")


def test_every_file_under_paths_is_named_by_benchmark():
    configs = {c["file"] for c in BENCH["configs"]}
    kinds = {json.load(open(os.path.join(ROOT, f)))["kind"] for f in configs}
    configs |= {f"bench/kinds/{k}.py" for k in kinds}
    configs |= {f"bench/limits/{k}.json" for k in kinds}
    traffic = {f"bench/traffic/{w['traffic']}.json"
               for w in BENCH["workloads"]}
    metrics = {f"bench/metrics/{m['name']}.py" for m in BENCH["per_layer"]}
    for path in configs | traffic | metrics:
        assert os.path.isfile(os.path.join(ROOT, path)), path


def test_rate_is_all_work_over_all_time():
    spans = [(10.0, 12.0), (12.0, 15.0), (15.0, 16.0)]
    assert harness.window_rate(spans, [16, 16, 16]) == pytest.approx(48 / 6)
    assert harness.window_rate(spans, [8, 16, 24]) == pytest.approx(48 / 6)
    with pytest.raises(ValueError):
        harness.window_rate([], [])


def test_refuses_a_device_that_is_not_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(harness.env, "use_compile_cache", lambda root: None)
    rc = harness.run_cell(ROOT, "fig2b-grid", 1, 0.1, False, 0.0)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_run_py_prints_no_result_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "fig2b-grid", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name = name
        self.start_ns, self.duration_ns = start_ns, duration_ns


def test_trace_reduction_busy_union_kernel_time_and_gaps():
    host = _Plane("/host:CPU", [_Line("python", [
        _Event("bench.window", 1000, 10_000),
        _Event("bench.call", 1000, 4000),
        _Event("bench.call", 6000, 5000),
        _Event("$engine.py:1207 _round_sweep", 7500, 3400),
    ])])
    ops = [_Event("while", 1500, 2000),           # 1500-3500
           _Event("waterfill_kernel", 2000, 500),  # inside the while
           _Event("fusion", 3000, 1500),          # 3000-4500
           _Event("fusion", 7000, 1000),          # 7000-8000
           _Event("fusion", 10_500, 2000)]        # clipped at 11000
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", ops),
                                   _Line("XLA Modules", [
                                       _Event("jit_program", 0, 20_000)])])
    s = trace.reduce(type("P", (), {"planes": [host, dev]})())
    assert s.window_s == pytest.approx(10_000e-9)
    busy = (4500 - 1500) + 1000 + 500
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.op_seconds(lambda n: "waterfill" in n) == pytest.approx(500e-9)
    assert s.ops["fusion"] == pytest.approx(3000e-9)
    # gaps: 1000-1500 in the first call, 4500-7000 centred between the
    # calls, 8000-10500 in the second call, inside the host's engine
    assert s.breakdown()["idle_gaps"] == [
        ["between calls", pytest.approx(2500e-9)],
        ["bench.call / $engine.py:1207 _round_sweep",
         pytest.approx(2500e-9)],
        ["bench.call", pytest.approx(500e-9)]]
    run = harness.Run(4, s, 0, {})
    idle_pct = harness.load_reader("device_idle_pct.sim")(run)
    assert idle_pct == pytest.approx(100 * (1 - busy / 10_000))
    per_round = harness.load_reader("device_ms_per_round.sim")(run)
    assert per_round == pytest.approx(1e3 * busy * 1e-9 / 4)
    wf = harness.load_reader("waterfill_ms_per_round")(run)
    assert wf == pytest.approx(1e3 * 500e-9 / 4)


def test_op_names_are_hlo_instruction_names():
    text = ("%while.63 = (f32[8,128]{1,0:T(8,128)}, s32[]{:T(128)}) "
            "while((f32[8,128]{1,0:T(8,128)}) %tuple.1), condition=%c")
    assert trace.op_name(text) == "while.63 (while)"
    text = ("%waterfill_grants_pallas.1 = f32[16,128]{1,0:T(8,128)} "
            "custom-call(%copy-done), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(text) == "waterfill_grants_pallas.1 (custom-call)"


def test_trace_without_window_or_kernel_reads_nothing():
    host = _Plane("/host:CPU", [_Line("python", [_Event("x", 0, 10)])])
    assert trace.reduce(type("P", (), {"planes": [host]})()) is None
    run = harness.Run(4, None, 0, {})
    for name in ("device_idle_pct.sim", "device_ms_per_round.sim",
                 "waterfill_ms_per_round"):
        assert harness.load_reader(name)(run) is None


def test_recorded_trace_reduces():
    """A trace recorded on a TPU v5e: two calls of a small jitted
    program inside a ``bench.window`` span."""
    import jax

    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_window.xplane.pb")
    s = trace.reduce(jax.profiler.ProfileData.from_file(path))
    assert s is not None
    assert 0 < s.busy_s < s.window_s
    assert s.ops and s.breakdown()["idle_gaps"]
