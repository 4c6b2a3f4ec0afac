"""``bench/layers.py``: the layer reduction of a profiler trace, on the
CPU, from synthetic planes / lines / events and from the committed
trace recorded on a TPU v5e."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import layers, trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_window.xplane.pb")


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


def _op(name, start, duration):
    return _Event(f"%{name} = f64[8]{{0}} {name.split('.')[0]}(%p)",
                  start, duration)


SCOPES = {"jit_ponsim_fcfs_fast": {"conditional.22": "sampler",
                                   "fusion.1": "sampler",
                                   "fusion.2": "grants",
                                   "fusion.3": "serve"},
          "jit_ponsim_bs": {"fusion.2": "serve"}}


def _profile():
    """One call in a 10,000 ns window: an fcfs program (a loop whose
    body nests the sampler's conditional over its own op), then a bs
    program, with host spans around and between them."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Event("bench.window", 1000, 10_000),
        _Event("bench.call", 1000, 10_000),
        _Event("net.simulate", 1000, 10_000),
        _Event("net.layout", 1000, 1000),            # 1000-2000
        _Event("$engine.py:1327 _round_sweep", 1100, 500),
        _Event("net.phase#phase=ul#", 2000, 4000),   # 2000-6000
        _Event("ponsim.run", 2000, 3000),            # 2000-5000
        _Event("ponsim.fetch", 5000, 800),           # 5000-5800
        _Event("net.slots", 6000, 1000),             # 6000-7000
        _Event("net.phase", 7000, 2000),             # 7000-9000
        _Event("ponsim.run", 7000, 2000),
        _Event("net.assemble", 10_000, 500),         # 10000-10500
    ])])
    modules = [_Event("jit_ponsim_fcfs_fast(123)", 2000, 3000),
               _Event("jit_ponsim_bs(456)", 7000, 1500),
               _Event("jit_ponsim_fcfs_fast(123)", 10_800, 1000)]
    ops = [_op("while.63", 2000, 3000),          # no scope: the loop
           _op("conditional.22", 2100, 800),     # sampler 2100-2900
           _op("fusion.1", 2200, 300),           # inside it: counts once
           _op("fusion.2", 3000, 1000),          # grants 3000-4000
           _op("fusion.3", 4000, 900),           # serve 4000-4900
           _op("fusion.9", 4900, 50),            # no scope: loop control
           _op("fusion.2", 7000, 1500),          # bs: serve 7000-8500
           _op("fusion.3", 10_800, 1000)]        # serve, cut at 11000
    dev = _Plane("/device:TPU:0", [_Line("XLA Modules", modules),
                                   _Line("XLA Ops", ops)])
    return type("P", (), {"planes": [host, dev]})()


def test_names_of_modules_ops_and_spans():
    assert layers.module_name("jit_ponsim_bs(7278514197239283955)") == (
        "jit_ponsim_bs")
    assert layers.instruction(
        "%while.63 = (f32[8,128]{1,0}) while((f32[8,128]{1,0}) %t)") == (
        "while.63")
    assert layers.span_name("net.phase#phase=ul,mode=fcfs#") == "net.phase"
    for other in ("bench.call", "$engine.py:1327 _round_sweep",
                  "<string>:2 __init__", "dataclasses.py:1540 replace"):
        assert layers.span_name(other) is None


def test_modules_scopes_spans_are_unions_inside_the_window():
    out = layers.reduce(_profile(), SCOPES)
    assert out.window_s == pytest.approx(10_000e-9)
    assert out.modules == {
        "jit_ponsim_fcfs_fast": pytest.approx(3200e-9),
        "jit_ponsim_bs": pytest.approx(1500e-9)}
    assert out.scopes == {
        "sampler": pytest.approx(800e-9),
        "grants": pytest.approx(1000e-9),
        "serve": pytest.approx(900e-9 + 1500e-9 + 200e-9)}
    assert out.spans["net.phase"] == pytest.approx(6000e-9)
    assert out.spans["ponsim.run"] == pytest.approx(5000e-9)
    assert out.spans["net.assemble"] == pytest.approx(500e-9)
    assert "bench.call" not in out.spans
    assert out.spans_union_s(layers.HOST_PREP) == pytest.approx(
        (1000 + 800 + 1000 + 500) * 1e-9)
    assert layers.reduce(_profile()).scopes == {}


def test_what_lies_outside_the_scopes():
    out = layers.reduce(_profile(), SCOPES)
    # fcfs: 3200 in the module, ops other than the loop cover 2950
    assert out.unscoped == {
        "jit_ponsim_fcfs_fast/fusion.9 (fusion)": pytest.approx(50e-9),
        "jit_ponsim_fcfs_fast/loop only": pytest.approx(250e-9),
        "jit_ponsim_bs/loop only": 0.0}


def test_every_idle_gap_goes_to_the_innermost_program_span():
    out = layers.reduce(_profile(), SCOPES)
    # busy 2000-5000, 7000-8500, 10800-11000
    assert out.busy_s == pytest.approx(4700e-9)
    assert out.idle_by_span == {
        "net.layout": pytest.approx(1000e-9),       # 1000-2000
        "net.slots": pytest.approx(2000e-9),        # 5000-7000, mid 6000
        "none": pytest.approx(2300e-9)}             # 8500-10800, mid 9650
    assert sum(out.idle_by_span.values()) == pytest.approx(
        out.window_s - out.busy_s)


def test_readings_per_round():
    out = layers.readings(layers.reduce(_profile(), SCOPES), rounds=2,
                          cycles=700)
    assert out["fcfs_program_ms_per_round"] == pytest.approx(1.6e-3)
    assert out["bs_program_ms_per_round"] == pytest.approx(0.75e-3)
    assert out["sampler_ms_per_round"] == pytest.approx(0.4e-3)
    assert out["serve_ms_per_round"] == pytest.approx(1.3e-3)
    assert out["host_prep_ms_per_round"] == pytest.approx(1.65e-3)
    assert out["idle_unattributed_pct"] == pytest.approx(100 * 2300 / 5300)
    assert out["loop_cycles_per_round"] == 350


def test_no_window_reads_nothing():
    host = _Plane("/host:CPU", [_Line("python", [_Event("x", 0, 10)])])
    assert layers.reduce(type("P", (), {"planes": [host]})()) is None


def test_recorded_trace_reduces_as_before():
    """The committed v5e trace: the accepted reduction reads what it
    read when the trace was recorded, and the layer reduction finds its
    one program and puts all idle time down to no program span."""
    import jax

    profile = jax.profiler.ProfileData.from_file(FIXTURE)
    s = trace.reduce(profile)
    assert s.window_s == pytest.approx(0.02170162, rel=1e-12)
    assert s.busy_s == pytest.approx(5.68e-07, rel=1e-12)
    assert s.ops == {"copy-start (copy-start)": pytest.approx(1.3e-08),
                     "copy-done (copy-done)": pytest.approx(2e-09),
                     "fusion (fusion)": pytest.approx(5.53e-07)}
    assert [label for label, _ in s.idle] == [
        "between calls / $time sleep"] * 4
    out = layers.reduce(profile)
    assert out.modules == {"jit__lambda": pytest.approx(5.75e-07)}
    assert out.spans == {} and out.scopes == {}
    assert out.idle_by_span == {"none": pytest.approx(
        out.window_s - out.busy_s)}
