"""Reduction of a profiler trace of the measured window to numbers.

The window is the host span ``bench.window`` that the harness records
around the calls it traces.  On each device plane, busy time is the
union of the intervals of its operations (the ``XLA Ops`` line, where a
loop's event spans its body's) inside the window, and an operation's
time is the sum of its events' durations there, under its HLO
instruction name.  An idle gap is a stretch of the window in which no
operation ran on the device; the longest are labelled by the innermost
span of the benchmark's own (``bench.*``) and the innermost other event
of the same host thread (JAX's Python tracer records function calls)
that cover its middle.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def start(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)


def stop(trace_dir: str):
    """Stop the trace, reduce it and delete it; ``None`` when the trace
    holds no window."""
    import jax

    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    try:
        if not files:
            return None
        return reduce(jax.profiler.ProfileData.from_file(files[0]))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """``"while.63 (while)"`` for an HLO instruction's text
    ``"%while.63 = (...) while(...), ..."``."""
    head, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end)]``."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals, lo, hi):
    """Stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


class Summary:
    """Busy time, per-operation time and the longest idle gaps of the
    window, in seconds, averaged over the device planes."""

    def __init__(self, window_s, busy_s, ops, idle):
        self.window_s = window_s
        self.busy_s = busy_s
        self.ops = ops            # op name -> seconds
        self.idle = idle          # [(label, seconds)], longest first

    def op_seconds(self, match) -> float:
        return sum(s for name, s in self.ops.items() if match(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle[:top]]}


def _host_events(profile):
    """The benchmark's spans and the other events of the host threads
    that record them: ``(spans, others)`` as ``(start, end, name)``."""
    spans, others = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events]
            if any(n.startswith(SPAN_PREFIX) for _, _, n in evs):
                for e in evs:
                    (spans if e[2].startswith(SPAN_PREFIX) else
                     others).append(e)
    return spans, others


def reduce(profile, top: int = 10) -> "Summary | None":
    """Summary of a ``jax.profiler.ProfileData`` (or anything with its
    ``planes`` / ``lines`` / ``events`` shape)."""
    spans, others = _host_events(profile)
    window = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if not window:
        return None
    lo, hi = window[0]
    devices = [p for p in profile.planes
               if p.name.startswith(DEVICE_PREFIX)]
    busy, ops, idle, names = 0.0, {}, [], {}
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, lo)
                b = min(ev.start_ns + ev.duration_ns, hi)
                if b <= a:
                    continue
                intervals.append((a, b))
                name = names.get(ev.name)
                if name is None:
                    name = names[ev.name] = op_name(ev.name)
                ops[name] = ops.get(name, 0.0) + (b - a)
        busy += union_length(intervals)
        idle += gaps(intervals, lo, hi)
    n = max(len(devices), 1)

    def innermost(events, mid, default):
        inner = [(e - s, name) for s, e, name in events
                 if s <= mid <= e and name != WINDOW_SPAN]
        return min(inner)[1] if inner else default

    longest = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        span = innermost(spans, mid, "between calls")
        host = innermost(others, mid, "")
        longest.append((f"{span} / {host}" if host else span,
                        (b - a) / 1e9))
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                   ops={k: v / 1e9 / n for k, v in ops.items()},
                   idle=longest)
