"""Run one benchmark cell once and print its result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``; its deployment in the file that ``configs`` names;
the deployment's ``kind``, whose module ``bench/kinds/<kind>.py`` builds,
times and checks the cell (see :mod:`bench.kinds`), with the limits of
its check in ``bench/limits/<kind>.json``; its traffic mix in
``bench/traffic/<traffic>.json``; and each per-layer metric in
``bench/metrics/<metric>.py`` (a ``read(run)`` function that returns a
number, or ``None`` where it finds nothing to read).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time

from bench import env, trace

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` with its deployment, traffic mix and the
    metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_kind(kind: str):
    """The module ``bench/kinds/<kind>.py``."""
    return importlib.import_module(f"bench.kinds.{kind}")


def load_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def limits(kind: str) -> dict:
    """The limits of the check of cells of ``kind``."""
    with open(os.path.join(HERE, "limits", kind + ".json")) as f:
        return json.load(f)


def check_devices(jax, chips: int):
    """The devices the cell runs on; raises :class:`NoChip` unless JAX's
    first device is a TPU and there are at least ``chips`` of them."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX has {len(devs)}")
    env.peaks(devs[0].device_kind)
    return devs[:chips]


def window_rate(spans, works) -> float:
    """Work per second over whole calls: all the work ``works`` of the
    calls in ``spans`` [(start, end)] over the time from the first start
    to the last end."""
    if not spans:
        raise ValueError("no call completed")
    return sum(works) / (spans[-1][1] - spans[0][0])


class Run:
    """What a per-layer reader sees of one traced run."""

    def __init__(self, work, trace_summary, compiles, counters):
        self.work = work                  # work units of the traced call
        self.trace = trace_summary        # bench.trace.Summary or None
        self.compiles = compiles          # backend compiles in the window
        self.counters = counters          # program counters' window deltas


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, t_process: float) -> int:
    """``bench/run.py``: one run of the cell ``workload``."""
    cell = load_cell(root, workload)
    env.use_compile_cache(root)
    import jax

    try:
        devs = check_devices(jax, cell["cell"]["chips"])
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    return execute(root, cell, devs, seed, seconds, traced, t_process)


def execute(root, cell, devs, seed, seconds, traced, t_process,
            control=False) -> int:
    """Set up, measure, check and print the result line of one run of
    ``cell`` (as :func:`load_cell` gives it) on ``devs``; with
    ``control`` the kind's control stands in the program's place."""
    import jax

    kind_name = cell["config"]["kind"]
    kind = load_kind(kind_name)
    compiles = env.Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    work = (kind.Control if control else kind.Cell)(
        cell["config"], cell["traffic"], seed)

    # set-up: inputs from the seed, then one call of the cell's shapes
    with jax.profiler.TraceAnnotation("bench.warmup"):
        work.warmup()
    setup_s = time.time() - t_process

    # window: whole calls until ``seconds`` have passed; a traced run
    # traces its first call only, since a call's trace alone is ~100 MB
    trace_dir = os.path.join(root, ".bench_trace")
    n0, counters0 = compiles.n, work.counters()
    spans, works, summary = [], [], None
    index = 1
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        inputs = work.inputs(index)
        tracing = traced and index == 1
        if tracing:
            trace.start(trace_dir)
        with jax.profiler.TraceAnnotation(
                "bench.window" if tracing else "bench.untraced"):
            with jax.profiler.TraceAnnotation("bench.call"):
                start = time.perf_counter()
                out = work.run(inputs)
                spans.append((start, time.perf_counter()))
        if tracing:
            summary = trace.stop(trace_dir)
            if summary is None:
                raise RuntimeError("the profiler trace holds no window")
        works.append(work.record(inputs, out))
        index += 1
    n_compiles = compiles.n - n0
    counters = {k: v - counters0.get(k, 0)
                for k, v in work.counters().items()}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    # correctness, once the window has closed and its peak is read
    with jax.profiler.TraceAnnotation("bench.reference"):
        checks, failed = work.check(limits(kind_name))
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if traced:
        run = Run(works[0], summary, n_compiles, counters)
        metrics = {}
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s,
                  kind.RATE: window_rate(spans, works)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    line = {"correct": bool(correct), "attempted": sum(works),
            "failed": failed, "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["breakdown"] = summary.breakdown()
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
