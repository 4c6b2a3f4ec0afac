"""Multi-round timeline engine throughput vs the per-round loop.

The paper's Fig. 3 quantities are multi-round: R synchronisation rounds
with elastic client membership. This benchmark drives the (policy ×
load) grid of the Fig. 3 operating point over R rounds two ways —

* ``timeline``: ONE stacked simulation (round axis folded into the
  engine batch, ``repro.net.timeline``);
* ``per-round``: the sequential timeline driver (``mode="sequential"``)
  — one engine call per round, queue state rebuilt every round (what
  ``FLNetworkCoSim`` did before the timeline backend; elastic
  membership defeats its fixed-client-set cache);

plus timeline rounds/sec at growing ONU counts, and a module-aggregated
profile of the folded run showing where time goes (the counter-based
sampler must not dominate — it replaced numpy draws that were ~1/4 of
engine time).

``python benchmarks/timeline.py --full --json BENCH_timeline.json``
measures the full R=24 sweep and writes the checked-in JSON; the
harness ``run()`` (slow tier — CI runs this module once, via its
dedicated ``BENCH_timeline.json`` step) times a reduced configuration.
"""
from __future__ import annotations

import argparse
import cProfile
import json
import pstats
import time

import numpy as np

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
)

TIER = "slow"                     # CI's dedicated step runs it instead

M_BITS = 26.416e6
N_ONUS = 128
PARTICIPATION = 0.8
# FL-transfer-dominated operating point: background traffic present but
# light, so the round is governed by the model uploads themselves (the
# regime the paper's slicing argument targets, and where the folded jit
# engine's scalar-S fast path pays off most).
FL_LOAD = 0.05


def _clients(n, seed=42):
    rng = np.random.default_rng(seed)
    t_uds = rng.uniform(1.0, 5.0, n)
    return [
        ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                      m_ud_bits=M_BITS)
        for i in range(n)
    ]


def fig3_cases(n_onus=N_ONUS, loads=(0.3, 0.8), seed=0):
    wl = FLRoundWorkload(clients=_clients(n_onus), model_bits=M_BITS)
    return [
        SweepCase(workload=wl, load=load, policy=policy, seed=seed)
        for policy in ("fcfs", "bs") for load in loads
    ]


def elastic_schedule(n_rounds, n_clients=N_ONUS, seed=7):
    memb = (np.random.default_rng(seed).random((n_rounds, n_clients))
            < PARTICIPATION)
    memb[0] = True
    return TimelineSchedule(n_rounds=n_rounds, membership=memb)


def _best_of(f, repeats):
    best, out = float("inf"), None
    for _ in range(max(repeats, 1)):
        t0 = time.time()
        out = f()
        best = min(best, time.time() - t0)
    return best, out


def profile_shares(cfg, cases, schedule):
    """Module-aggregated tottime of one folded run: the sampler
    (kernels/traffic) vs the engine cycle loop (net/engine+timeline)."""
    prof = cProfile.Profile()
    prof.enable()
    simulate(SweepSpec(cases=tuple(cases), pon=cfg,
                       schedule=schedule, mode="folded"))
    prof.disable()
    stats = pstats.Stats(prof)
    shares = {"kernels/traffic": 0.0, "net/engine": 0.0, "other": 0.0}
    top_name, top_t = "", 0.0
    total = 0.0
    for (fname, _, func), (_, _, tottime, _, _) in stats.stats.items():
        total += tottime
        if "kernels/traffic" in fname:
            shares["kernels/traffic"] += tottime
        elif "net/engine" in fname or "net/timeline" in fname:
            shares["net/engine"] += tottime
        else:
            shares["other"] += tottime
        if tottime > top_t:
            top_t, top_name = tottime, f"{fname.split('/')[-1]}:{func}"
    return {
        "total_s": total,
        "shares": {k: v / max(total, 1e-9) for k, v in shares.items()},
        "top_function": top_name,
        "sampler_is_top_module": (
            shares["kernels/traffic"] >= shares["net/engine"]
        ),
    }


def throughput(n_onus_grid=(128, 512, 2048), n_rounds=4, load=0.8,
               backend=None):
    """Timeline rounds/sec at growing ONU counts (line rate scaled so
    the offered load stays feasible, as in benchmarks/net_engine.py).

    ``backend="jit"`` times the device cycle engine; one untimed
    warm-up run per shape pays the one-compile-per-schedule-shape cost
    up front (the documented usage model), so the rows measure steady
    throughput for both backends alike.
    """
    out = []
    for n in n_onus_grid:
        cfg = PONConfig(n_onus=n, line_rate_bps=10e9 * n / 128)
        wl = FLRoundWorkload(clients=_clients(n), model_bits=M_BITS)
        sched = elastic_schedule(n_rounds, n)
        case = [SweepCase(workload=wl, load=load, policy="fcfs",
                          seed=0)]
        spec = SweepSpec(cases=tuple(case), pon=cfg, schedule=sched,
                         backend=backend)
        if backend is not None:
            simulate(spec)
        t0 = time.time()
        res = simulate(spec)[0]
        wall = time.time() - t0
        out.append({
            "n_onus": n,
            "wall_s": wall,
            "rounds_per_sec": n_rounds / wall,
            "mean_sync_s": float(res.sync_times.mean()),
        })
    return out


def _attach_speedup(jit_rows, numpy_rows):
    """Stamp per-row jit-vs-numpy speedup (matched n_onus)."""
    base = {r["n_onus"]: r["wall_s"] for r in numpy_rows}
    for r in jit_rows:
        if r["n_onus"] in base:
            r["speedup_vs_numpy"] = base[r["n_onus"]] / r["wall_s"]
    return jit_rows


def stacked_run(n_pons=100, onus_per_pon=1024, n_rounds=2,
                load=FL_LOAD):
    """The 100k-ONU x 100-PON stacked device run: every round of every
    PON of the whole deployment folded into ONE jit device program.
    Far beyond interactive numpy reach, so the row records completion
    + throughput of the jit backend only."""
    from repro.net import MultiPonTopology

    n_total = n_pons * onus_per_pon
    cfg = PONConfig(n_onus=onus_per_pon,
                    line_rate_bps=10e9 * onus_per_pon / 128)
    wl = FLRoundWorkload(clients=_clients(onus_per_pon),
                         model_bits=M_BITS)
    topo = MultiPonTopology(n_pons=n_pons)
    sched = elastic_schedule(n_rounds, onus_per_pon)
    cases = [SweepCase(workload=wl, load=load, policy="fcfs", seed=0,
                       topology=topo)]
    t0 = time.time()
    res = simulate(SweepSpec(cases=tuple(cases), pon=cfg,
                             schedule=sched, backend="jit"))[0]
    wall = time.time() - t0
    return {
        "n_onus_total": n_total,
        "n_pons": n_pons,
        "onus_per_pon": onus_per_pon,
        "n_rounds": n_rounds,
        "load": load,
        "completed": len(res.rounds) == n_rounds,
        "wall_s": wall,
        "rounds_per_sec": n_rounds / wall,
        "mean_sync_s": float(res.sync_times.mean()),
    }


def measure(full: bool = False) -> dict:
    """The BENCH_timeline.json payload."""
    n_rounds = 24 if full else 6
    grid = (128, 512, 2048) if full else (128, 512)
    fl_grid = (512, 2048)
    cfg = PONConfig(n_onus=N_ONUS)
    cases = fig3_cases()
    sched = elastic_schedule(n_rounds)
    # warm allocators, jit caches and sampler LUTs
    simulate(SweepSpec(cases=tuple(cases[:1]), pon=cfg,
                       schedule=elastic_schedule(1)))

    fold_wall, fold = _best_of(
        lambda: simulate(SweepSpec(cases=tuple(cases), pon=cfg,
                                   schedule=sched, mode="folded")),
        repeats=3 if full else 2,
    )
    per_round_wall, per_round = _best_of(
        lambda: simulate(SweepSpec(cases=tuple(cases), pon=cfg,
                                   schedule=sched, mode="sequential")),
        repeats=2 if full else 1,
    )
    assert all(
        np.allclose(a.sync_times, b.sync_times, rtol=1e-9)
        for a, b in zip(fold, per_round)
    ), "folded and per-round timelines diverged"
    tp = throughput(grid)
    fl_np = throughput(fl_grid, load=FL_LOAD)
    payload = {
        "benchmark": "fig3_multiround_timeline_vs_per_round",
        "n_onus": N_ONUS,
        "n_rounds": n_rounds,
        "participation": PARTICIPATION,
        "sweep_cells": len(cases),
        "timeline_wall_s": fold_wall,
        "per_round_wall_s": per_round_wall,
        "speedup": per_round_wall / fold_wall,
        "rounds_per_sec_sweep": n_rounds * len(cases) / fold_wall,
        "sync_times_s": {
            f"{c.policy}_load{c.load}": [round(float(s), 4)
                                         for s in r.sync_times]
            for c, r in zip(cases, fold)
        },
        "profile": profile_shares(cfg, cases, sched),
        "throughput": tp,
        # backend-keyed rows: the jit regression gate.  Two operating
        # points per backend — the fig3 load (0.8, both engines
        # sampler-bound on CPU, jit must hold parity) and the
        # FL-dominated light load where the device engine's folded
        # scalar-S fast path delivers its >=5x at 2048+ ONUs.
        "fl_load": FL_LOAD,
        "throughput_jit": _attach_speedup(
            throughput(grid, backend="jit"), tp),
        "throughput_fl": fl_np,
        "throughput_fl_jit": _attach_speedup(
            throughput(fl_grid, load=FL_LOAD, backend="jit"), fl_np),
    }
    if full:
        # the 100k-ONU x 100-PON regime: one folded jit device program
        payload["stacked"] = stacked_run()
    return payload


def run() -> list:
    m = measure(full=False)
    rows = [
        {
            "name": "timeline_fig3_multiround_sweep",
            "us_per_call": m["timeline_wall_s"] * 1e6,
            "derived": (
                f"rounds={m['n_rounds']} "
                f"speedup_vs_per_round={m['speedup']:.1f}x "
                f"sampler_share="
                f"{m['profile']['shares']['kernels/traffic']:.2f}"
            ),
        }
    ]
    for key, suffix in (("throughput", ""), ("throughput_jit", "_jit"),
                        ("throughput_fl", "_fl"),
                        ("throughput_fl_jit", "_fl_jit")):
        for tp in m[key]:
            extra = (f" speedup_vs_numpy={tp['speedup_vs_numpy']:.2f}x"
                     if "speedup_vs_numpy" in tp else "")
            rows.append({
                "name": f"timeline_rounds_n{tp['n_onus']}{suffix}",
                "us_per_call": tp["wall_s"] * 1e6,
                "derived": (
                    f"rounds_per_sec={tp['rounds_per_sec']:.2f} "
                    f"mean_sync_s={tp['mean_sync_s']:.2f}" + extra
                ),
            })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="measure the full R=24 sweep (minutes)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the measurement payload as JSON")
    args = ap.parse_args()
    m = measure(full=args.full)
    print(json.dumps(m, indent=2))
    if args.json:
        from benchmarks._env import stamp

        with open(args.json, "w") as f:
            json.dump(stamp(m), f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
