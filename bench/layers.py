#!/usr/bin/env python3
"""Layer breakdown of one traced call of a cell, in the program's names.

    python3 bench/layers.py --workload fig2b-grid --seed 7 --calls 5

Sets the cell up as ``bench/run.py`` does, times ``--calls`` untraced
calls and then one traced call, all on the inputs of window call 1, and
prints one JSON line: the traced call against the untraced median (the
cost of tracing), the accepted per-layer readings of the trace (idle,
device and waterfill time), and the readings below, which name the
program's own parts.  Exits
non-zero, with no result line, unless JAX's first device is a TPU.

The reduction (:func:`reduce`) reads the same profiler trace as
:mod:`bench.trace`, inside the same ``bench.window`` span.  Every total
is a union of intervals inside the window, so nested events (a loop and
its body's ops) count once, averaged over the device planes:

* ``modules``: device seconds per program, from the ``XLA Modules``
  line (``jit_ponsim_fcfs_fast``; the ``(id)`` suffix is dropped);
* ``scopes``: device seconds per named scope of the phase program's
  loop body, over the ``XLA Ops`` events; an op belongs to the module
  whose module event holds it, and ``scope_map`` (from
  ``repro.kernels.ponsim.ops.scope_map``) names its scope;
* ``spans``: host seconds per program span (``<layer>.<step>`` events
  of the thread that records the ``bench.*`` spans, ``#...#`` metadata
  dropped);
* ``idle_by_span``: every idle gap of the device, put down to the
  innermost program span other than ``net.simulate`` that covers its
  middle, or ``none``;
* ``unscoped``: what a phase program's device time holds outside its
  scopes: each unscoped op (``<module>/<op>``, summed durations) and,
  as ``<module>/loop only``, the time in which the module ran but no
  op other than a ``while`` loop did (loop control).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULES_LINE = "XLA Modules"
CALL_SPAN = "net.simulate"
# the spans of host work with no device work under them
HOST_PREP = ("net.layout", "net.slots", "net.assemble", "ponsim.inputs",
             "ponsim.put", "ponsim.fetch", "timeline.setup",
             "timeline.views")
_PROGRAM_SPAN = re.compile(r"^[a-z_]+\.[a-z_]+$")


def module_name(event_name: str) -> str:
    """``"jit_ponsim_bs"`` for ``"jit_ponsim_bs(7278514197239283955)"``."""
    return event_name.split("(", 1)[0]


def instruction(event_name: str) -> str:
    """``"while.63"`` for the op text ``"%while.63 = (...) while(...)"``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def span_name(event_name: str) -> "str | None":
    """The program span an event of the host thread stands for (its
    ``#k=v#`` metadata dropped), or ``None`` for any other event."""
    name = event_name.split("#", 1)[0]
    if name.startswith("bench.") or not _PROGRAM_SPAN.match(name):
        return None
    return name


class Layers:
    """Seconds per module, scope, program span and idle attribution of
    the window (see the module docstring)."""

    def __init__(self, window_s, busy_s, modules, scopes, spans,
                 idle_by_span, span_intervals, unscoped=None):
        self.window_s = window_s
        self.busy_s = busy_s
        self.modules = modules
        self.scopes = scopes
        self.spans = spans
        self.idle_by_span = idle_by_span
        self.unscoped = unscoped or {}
        self._span_intervals = span_intervals

    def spans_union_s(self, names) -> float:
        """Host seconds covered by any of the spans ``names``."""
        from bench.trace import union_length

        iv = [x for n in names for x in self._span_intervals.get(n, ())]
        return union_length(iv) / 1e9


def _clip(events, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if min(b, hi) > max(a, lo)]


def reduce(profile, scope_map=None) -> "Layers | None":
    """:class:`Layers` of a ``jax.profiler.ProfileData`` (or anything
    with its planes / lines / events shape); ``None`` without a
    ``bench.window`` span.  ``scope_map`` is ``{module: {instruction:
    scope}}``; without it ``scopes`` is empty."""
    from bench.trace import (DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN,
                             _host_events, gaps, op_name, union_length)

    bench_spans, others = _host_events(profile)
    window = [(a, b) for a, b, n in bench_spans if n == WINDOW_SPAN]
    if not window:
        return None
    lo, hi = window[0]
    spans = []
    for a, b, n in _clip(others, lo, hi):
        name = span_name(n)
        if name is not None:
            spans.append((a, b, name))
    span_iv: dict = {}
    for a, b, n in spans:
        span_iv.setdefault(n, []).append((a, b))
    inner = [(a, b, n) for a, b, n in spans if n != CALL_SPAN]
    scope_map = scope_map or {}
    devices = [p for p in profile.planes
               if p.name.startswith(DEVICE_PREFIX)]
    busy, mod_s, scope_s, idle, unscoped = 0.0, {}, {}, {}, {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns,
                       module_name(ev.name))
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ())], lo, hi)
        mods.sort()
        per_mod: dict = {}
        for a, b, n in mods:
            per_mod.setdefault(n, []).append((a, b))
        for n, iv in per_mod.items():
            mod_s[n] = mod_s.get(n, 0.0) + union_length(iv)
        ops = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for ev in (lines[OPS_LINE].events
                                if OPS_LINE in lines else ())], lo, hi)
        busy += union_length([(a, b) for a, b, _ in ops])
        per_scope: dict = {}
        work: dict = {}                 # module -> its non-loop ops
        j = 0
        for a, b, text in sorted(ops):
            while j < len(mods) and mods[j][1] <= a:
                j += 1
            if j == len(mods) or mods[j][0] > a:
                continue
            module = mods[j][2]
            if module not in scope_map:
                continue
            scope = scope_map[module].get(instruction(text))
            if scope is not None:
                per_scope.setdefault(scope, []).append((a, b))
            name = op_name(text)
            if name.endswith(" (while)"):
                continue
            work.setdefault(module, []).append((a, b))
            if scope is None:
                key = f"{module}/{name}"
                unscoped[key] = unscoped.get(key, 0.0) + (b - a)
        for n, iv in per_scope.items():
            scope_s[n] = scope_s.get(n, 0.0) + union_length(iv)
        for n, iv in work.items():
            key = f"{n}/loop only"
            unscoped[key] = unscoped.get(key, 0.0) + (
                union_length(per_mod[n]) - union_length(iv))
        for a, b in gaps([(a, b) for a, b, _ in ops], lo, hi):
            mid = (a + b) / 2
            cover = [(e - s, n) for s, e, n in inner if s <= mid <= e]
            label = min(cover)[1] if cover else "none"
            idle[label] = idle.get(label, 0.0) + (b - a)
    k = 1e9 * max(len(devices), 1)
    return Layers(
        window_s=(hi - lo) / 1e9, busy_s=busy / k,
        modules={n: s / k for n, s in mod_s.items()},
        scopes={n: s / k for n, s in scope_s.items()},
        spans={n: union_length(iv) / 1e9 for n, iv in span_iv.items()},
        idle_by_span={n: s / k for n, s in idle.items()},
        span_intervals=span_iv,
        unscoped={n: s / k for n, s in unscoped.items()})


def readings(layers: "Layers", rounds: int, cycles: int) -> dict:
    """The layer readings of one traced call of ``rounds`` simulated
    rounds whose device loops ran ``cycles`` cycles."""
    def per_round(seconds):
        return 1e3 * seconds / rounds

    def programs(tag):
        return per_round(sum(s for n, s in layers.modules.items()
                             if tag in n.split("_")))

    idle = sum(layers.idle_by_span.values())
    out = {"fcfs_program_ms_per_round": programs("fcfs"),
           "bs_program_ms_per_round": programs("bs")}
    for scope, seconds in sorted(layers.scopes.items()):
        out[f"{scope}_ms_per_round"] = per_round(seconds)
    out["host_prep_ms_per_round"] = per_round(
        layers.spans_union_s(HOST_PREP))
    if idle:
        out["idle_unattributed_pct"] = (
            100.0 * layers.idle_by_span.get("none", 0.0) / idle)
    out["loop_cycles_per_round"] = cycles / rounds
    return out


def _stop(trace_dir, scope_map):
    """Stop the profiler; ``(bench.trace summary, Layers)`` of the trace,
    which is then deleted."""
    import jax

    from bench import trace

    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    try:
        profile = jax.profiler.ProfileData.from_file(files[0])
        return trace.reduce(profile), reduce(profile, scope_map())
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=5,
                    help="untraced calls timed before the traced one")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import env, harness, trace

    cell = harness.load_cell(ROOT, args.workload)
    env.use_compile_cache(ROOT)
    import jax

    try:
        harness.check_devices(jax, cell["cell"]["chips"])
    except harness.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    from repro.kernels.ponsim import ops

    kind = harness.load_kind(cell["config"]["kind"])
    work = kind.Cell(cell["config"], cell["traffic"], args.seed)
    work.warmup()
    inputs = work.inputs(1)
    untraced = []
    for _ in range(args.calls):
        t0 = time.perf_counter()
        work.run(inputs)
        untraced.append(time.perf_counter() - t0)
    trace_dir = os.path.join(ROOT, ".bench_trace")
    cycles0 = sum(ops.loop_cycles().values())
    trace.start(trace_dir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.call"):
            t0 = time.perf_counter()
            work.run(inputs)
            traced = time.perf_counter() - t0
    cycles = sum(ops.loop_cycles().values()) - cycles0
    summary, layers = _stop(trace_dir, ops.scope_map)
    rounds = work.rounds_per_call
    run = harness.Run(rounds, summary, 0, {})
    accepted = {name: harness.load_reader(name)(run)
                for name in ("device_idle_pct.sim", "device_ms_per_round.sim",
                             "waterfill_ms_per_round")}
    median = statistics.median(untraced)
    line = {
        "workload": args.workload, "seed": args.seed, "rounds": rounds,
        "device": jax.devices()[0].device_kind,
        "untraced_call_s": untraced, "traced_call_s": traced,
        "tracing_overhead_pct": 100.0 * (traced / median - 1.0),
        "accepted": accepted,
        "layers": readings(layers, rounds, cycles),
        "loop_cycles": ops.loop_cycles(),
        "window_s": layers.window_s, "busy_s": layers.busy_s,
        "modules_s": layers.modules, "scopes_s": layers.scopes,
        "spans_s": layers.spans, "idle_by_span_s": layers.idle_by_span,
        "unscoped_s": dict(sorted(layers.unscoped.items(),
                                  key=lambda kv: -kv[1])[:12]),
        "top_ops": summary.breakdown()["device_ops"],
    }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
