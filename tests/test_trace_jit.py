"""Tracing of the ``backend="jit"`` path from inside the program.

* every phase program is a module named for its spec, and its loop
  body's ops carry the ``sampler``/``push``/``grants``/``serve`` scopes;
* a ``repro.obs.Collector`` on the jit backend changes no result bit and
  records the ``net.*``, ``ponsim.*`` and ``timeline.*`` spans;
* the loop-cycle counter equals the numpy engine's per-phase cycles;
* the spans reach a ``jax.profiler`` trace with no collector at all;
* ``ops.scope_map`` names the scope of the compiled programs' ops;
* the bs programs' slot grants reach the ONUs without a scatter, and
  slots and ONUs map to each other without a gather;
* the fcfs programs' sampler refills its window without a loop.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.kernels.ponsim import ops
from repro.net import (
    FLRoundWorkload,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
)
from repro.obs import Collector, SpanTracer
from repro.obs.trace import maybe_span

CFG = PONConfig(n_onus=4, line_rate_bps=1e9)


def _workload(ids, seed=1):
    rng = np.random.default_rng(seed)
    return FLRoundWorkload(clients=[
        ClientProfile(client_id=int(i), t_ud=float(rng.uniform(0.05, 0.5)),
                      t_dl=0.0, m_ud_bits=float(rng.uniform(1e5, 2e6)))
        for i in ids], model_bits=1.5e6)


WL = _workload([0, 1, 2, 3])                 # one client per ONU
WL_MULTI = _workload([0, 1, 2, 3, 5, 9])     # several per ONU (fcfs)
WL_2PON = _workload(range(8))
CPS = MultiPonTopology(n_pons=2, cps_rate_bps=0.9 * 2 * 1e9)

# (cases, spec keywords) per kind of phase program
SWEEPS = {
    "fcfs_fast": ([SweepCase(workload=WL, load=0.8, policy="fcfs",
                             seed=3)], {}),
    "fcfs_many_per_onu": ([SweepCase(workload=WL_MULTI, load=0.8,
                                     policy="fcfs", seed=3)], {}),
    "bs": ([SweepCase(workload=WL, load=0.8, policy="bs", seed=3)], {}),
    "cps_both": ([SweepCase(workload=WL_2PON, load=0.8, policy=p, seed=5,
                            topology=CPS) for p in ("fcfs", "bs")], {}),
    "deadline": ([SweepCase(workload=WL, load=0.9, policy=p, seed=2)
                  for p in ("fcfs", "bs")], {"ul_deadline_s": (0.3, 0.4)}),
}


def _spec(name, backend="jit"):
    cases, kw = SWEEPS[name]
    return SweepSpec(cases=tuple(cases), pon=CFG, backend=backend, **kw)


def _phase_calls(spec):
    """Arguments of every phase a jit sweep hands the device engine."""
    calls = []
    real = ops.run_phase_device

    def record(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "run_phase_device", record)
        simulate(spec)
    return calls


def _round_key(res):
    """Every result value, exactly (``repr`` of a float is exact, and
    NaN reads equal to NaN)."""
    return repr((res.sync_time, res.dl_done, res.ready, res.ul_done,
                 res.ul_remaining))


@pytest.mark.parametrize("name", sorted(SWEEPS))
def test_phase_programs_are_named_and_scoped(name):
    for args, kw in _phase_calls(_spec(name)):
        mode = args[4]
        prog, dyn = ops.phase_program(*args, **kw)
        with jax.enable_x64(True):
            text = prog.lower(dyn).as_text(debug_info=True)
        assert prog.__name__.startswith(f"ponsim_{mode}")
        assert f"module @jit_{prog.__name__} " in text
        scopes = {s for s in ops.SCOPES if f"/while/body/{s}/" in text}
        want = {"push", "grants", "serve"}
        if mode == "fcfs":
            want.add("sampler")     # bs phases have no bg traffic
        assert scopes == want, (prog.__name__, scopes)


def test_program_names_follow_the_spec():
    names = {name: {ops.phase_program(*a, **kw)[0].__name__
                    for a, kw in _phase_calls(_spec(name))}
             for name in SWEEPS}
    assert names == {
        "fcfs_fast": {"ponsim_fcfs_fast"},
        "fcfs_many_per_onu": {"ponsim_fcfs"},
        "bs": {"ponsim_bs"},
        "cps_both": {"ponsim_fcfs_fast_cps", "ponsim_bs_cps"},
        "deadline": {"ponsim_fcfs_fast", "ponsim_fcfs_fast_deadline",
                     "ponsim_bs_deadline"},
    }


@pytest.mark.parametrize("name", sorted(SWEEPS))
def test_collector_on_jit_is_bitwise_and_records_spans(name):
    base = simulate(_spec(name))
    col = Collector(tracer=SpanTracer())
    inst = simulate(_spec(name), collector=col)
    assert [_round_key(r) for r in base] == [_round_key(r) for r in inst]
    names = {e["name"] for e in col.tracer.events}
    assert {"net.simulate", "net.layout", "net.phase", "net.assemble",
            "ponsim.inputs", "ponsim.put", "ponsim.run",
            "ponsim.fetch"} <= names
    assert ("net.slots" in names) == any(
        c.policy == "bs" for c in SWEEPS[name][0])
    phase_args = [e["args"] for e in col.tracer.events
                  if e["name"] == "net.phase"]
    assert {(a["phase"], a["mode"]) for a in phase_args} <= {
        ("dl", "fcfs"), ("ul", "fcfs"), ("dl", "bs"), ("ul", "bs")}
    report = col.report().to_dict()
    assert report["phases"] and all(
        set(p) == {"label", "rows", "cycles"} for p in report["phases"])
    assert report["delay_percentiles"]


@pytest.mark.parametrize("schedule,mode", [
    (TimelineSchedule(n_rounds=3), "folded"),
    (TimelineSchedule(n_rounds=2, deadline_s=0.4,
                      deadline_policy="defer"), "auto"),
], ids=["folded", "sequential"])
def test_collector_on_jit_timeline(schedule, mode):
    cases = tuple(SweepCase(workload=WL, load=0.6, policy=p, seed=4)
                  for p in ("fcfs", "bs"))
    spec = SweepSpec(cases=cases, pon=CFG, backend="jit",
                     schedule=schedule, mode=mode)
    base = simulate(spec)
    col = Collector(tracer=SpanTracer())
    inst = simulate(spec, collector=col)

    def key(res):
        return repr([(r.t_start, r.t_end, r.sync_time, r.arrived,
                      r.ul_bits, r.deferred) for r in res.rounds])

    assert [key(r) for r in base] == [key(r) for r in inst]
    names = {e["name"] for e in col.tracer.events}
    assert {"net.simulate", "timeline.setup", "timeline.views",
            "net.phase", "ponsim.run"} <= names
    per_round = "timeline.folded" if mode == "folded" else "timeline.round"
    assert per_round in names
    rounds = [e["args"]["round"] for e in col.tracer.events
              if e["name"] == "timeline.round"]
    assert rounds == ([] if mode == "folded" else [0, 1])


@pytest.mark.parametrize("name", sorted(SWEEPS))
def test_loop_cycles_equal_the_numpy_engine_cycles(name):
    host = Collector()
    simulate(_spec(name, backend="numpy"), collector=host)
    dev = Collector()
    before = ops.loop_cycles()
    simulate(_spec(name), collector=dev)
    after = ops.loop_cycles()

    def cycles(col):
        return [(p["label"], p["rows"], p["cycles"])
                for p in col.report().phases]

    assert cycles(dev) == cycles(host)
    added = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert sum(added.values()) == sum(c for _, _, c in cycles(host))
    assert all(k.startswith(("ponsim_fcfs", "ponsim_bs")) for k in added)


def test_spans_reach_the_profiler_without_a_collector(tmp_path):
    spec = _spec("bs")
    simulate(spec)                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with maybe_span(None, "test.outer", ignored=1):
            simulate(spec)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path[0])
    names = {ev.name for plane in profile.planes for line in plane.lines
             for ev in line.events}
    assert {"test.outer", "net.simulate", "net.layout", "net.slots",
            "net.phase", "ponsim.run", "ponsim.fetch"} <= names


def test_scope_map_names_the_scopes_of_the_cached_programs():
    ops.clear_cache()
    simulate(_spec("cps_both"))
    table = ops.scope_map()
    assert set(table) == {"jit_ponsim_fcfs_fast_cps", "jit_ponsim_bs_cps"}
    assert set(table["jit_ponsim_fcfs_fast_cps"].values()) == set(ops.SCOPES)
    assert set(table["jit_ponsim_bs_cps"].values()) == {"push", "grants",
                                                        "serve"}
    # the sampler's window refill is a conditional inside the loop body
    assert any(op.startswith("cond") and scope == "sampler"
               for op, scope in table["jit_ponsim_fcfs_fast_cps"].items())


HLO = """HloModule jit_ponsim_bs, is_scheduled=true

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(ponsim_bs)/while/body/grants/neg"}
}

%branch_1 (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  ROOT %copy.3 = f32[8]{0} copy(%arg)
}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.90 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %conditional.22 = f32[8]{0} conditional(%c, %fusion.90, %fusion.90), branch_computations={%branch_1, %branch_1}, metadata={op_name="jit(ponsim_bs)/while/body/sampler/cond"}
  ROOT %copy.9 = f32[8]{0} copy(%conditional.22)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %while.6 = f32[8]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(ponsim_bs)/while"}
}
"""


def test_hlo_scopes_fill_in_what_xla_left_without_metadata():
    scopes = ops.hlo_scopes(HLO)
    assert scopes["negate.1"] == "grants"
    assert scopes["fusion.90"] == "grants"      # from its fused computation
    assert scopes["conditional.22"] == "sampler"
    assert scopes["copy.3"] == "sampler"        # inside the branch it runs
    assert "copy.9" not in scopes               # loop glue stays unscoped
    assert "while.6" not in scopes


# an HLO instruction whose opcode is scatter (its type may be a tuple)
_SCATTER = re.compile(
    r"^\s+(?:ROOT )?%?([\w.-]+) = (?:\([^)]*\)|\S+) scatter\(", re.M)
OUTAGE = SweepSpec(cases=(SweepCase(workload=WL, load=0.8, policy="bs",
                                    seed=3),),
                   pon=CFG, backend="jit", ul_outage_s=[(0.1, 0.3)])


BS_PROGRAMS = {"bs": {"ponsim_bs"}, "cps_both": {"ponsim_bs_cps"},
               "deadline": {"ponsim_bs_deadline"},
               "outage": {"ponsim_bs_outage"}}


def _bs_compiled(name):
    """``(program name, HLO text, scopes, (R, S, N))`` of every bs phase
    of sweep ``name``, compiled for the CPU."""
    spec = OUTAGE if name == "outage" else _spec(name)
    out = []
    for args, kw in _phase_calls(spec):
        if args[4] != "bs":
            continue
        prog, dyn = ops.phase_program(*args, **kw)
        with jax.enable_x64(True):
            text = prog.lower(dyn).compile().as_text()
        shape = (*dyn["ts"].shape, args[0].n_onus)
        out.append((prog.__name__, text, ops.hlo_scopes(text), shape))
    return out


@pytest.mark.parametrize("name", sorted(BS_PROGRAMS))
def test_bs_slot_grants_compile_without_a_scatter(name):
    programs = set()
    for prog_name, text, scopes, _ in _bs_compiled(name):
        programs.add(prog_name)
        assert "grants" in scopes.values()
        assert [op for op in _SCATTER.findall(text)
                if scopes.get(op) == "grants"] == [], prog_name
    assert programs == BS_PROGRAMS[name]


# an HLO instruction whose opcode is gather: its name and result dims
_GATHER = re.compile(
    r"^\s+(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]*)\]\S* gather\(", re.M)


@pytest.mark.parametrize("name", sorted(BS_PROGRAMS))
def test_bs_slot_map_compiles_without_a_gather(name):
    """Slots reach ONUs, and ONUs slots, by a one-hot select: no gather
    of scope ``grants`` gives an ``(R, S)`` or ``(R, N)`` result, in
    whatever shape the compiler puts it (``f64[R*S,1,1]``); the CPS
    split's ``(R / P, 1)`` gather may stay."""
    programs = set()
    for prog_name, text, scopes, (R, S, N) in _bs_compiled(name):
        programs.add(prog_name)
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                 for op, dims in _GATHER.findall(text)
                 if scopes.get(op) == "grants"]
        assert R * S not in sizes and R * N not in sizes, prog_name
    assert programs == BS_PROGRAMS[name]


# an HLO instruction whose opcode is while
_WHILE = re.compile(
    r"^\s+(?:ROOT )?%?([\w.-]+) = (?:\([^)]*\)|\S+) while\(", re.M)


@pytest.mark.parametrize("name", ["fcfs_fast", "fcfs_many_per_onu",
                                  "cps_both", "deadline"])
def test_sampler_refill_compiles_without_a_loop(name):
    programs = set()
    for args, kw in _phase_calls(_spec(name)):
        if args[4] != "fcfs":
            continue
        prog, dyn = ops.phase_program(*args, **kw)
        programs.add(prog.__name__)
        with jax.enable_x64(True):
            text = prog.lower(dyn).compile().as_text()
        scopes = ops.hlo_scopes(text)
        assert "sampler" in scopes.values()
        assert _WHILE.findall(text), prog.__name__     # the cycle loop
        assert [op for op in _WHILE.findall(text)
                if scopes.get(op) == "sampler"] == [], prog.__name__
    assert programs == {
        "fcfs_fast": {"ponsim_fcfs_fast"},
        "fcfs_many_per_onu": {"ponsim_fcfs"},
        "cps_both": {"ponsim_fcfs_fast_cps"},
        "deadline": {"ponsim_fcfs_fast", "ponsim_fcfs_fast_deadline"},
    }[name]
