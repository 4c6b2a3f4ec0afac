"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

``--tier fast`` runs only the cheap tier (module attribute
``TIER == "fast"``; training/roofline modules are the slow tier);
``--json out.json`` additionally writes the rows (plus environment
metadata) as JSON — the artifact CI uploads; ``--profile`` stamps a
per-stage (per-module) wall-time breakdown into the payload ``meta``
block, which ``compare.py`` ignores (provenance, never a gated metric).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback

# make `python benchmarks/run.py` work from anywhere: the repo root (the
# ``benchmarks`` package's parent) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def collect_modules(tier: str):
    from benchmarks import (
        async_timeline,
        bs_micro,
        faults,
        fig2a_accuracy,
        fig2b_sync_time,
        jobs,
        multi_pon,
        net_engine,
        obs_overhead,
        roofline_report,
        timeline,
        training_time_saving,
    )

    # sorted by name so the row order (and CI log diff) is deterministic
    # regardless of how modules get added to this list
    modules = sorted(
        [
            ("bs_micro", bs_micro),
            ("fig2b_sync_time", fig2b_sync_time),
            ("training_time_saving", training_time_saving),
            ("net_engine", net_engine),
            ("multi_pon", multi_pon),
            ("jobs", jobs),
            ("timeline", timeline),
            ("async_timeline", async_timeline),
            ("faults", faults),
            ("obs_overhead", obs_overhead),
            ("fig2a_accuracy", fig2a_accuracy),
            ("roofline_report", roofline_report),
        ]
    )
    if tier == "all":
        return modules
    return [
        (name, mod) for name, mod in modules
        if getattr(mod, "TIER", "slow") == tier
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=("fast", "slow", "all"),
                    default="all", help="which benchmark tier to run")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows + metadata as JSON")
    ap.add_argument("--profile", action="store_true",
                    help="stamp per-stage wall-time breakdown into the "
                         "JSON meta block (compare.py-ignored)")
    args = ap.parse_args(argv)

    from benchmarks._env import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    modules = collect_modules(args.tier)
    print("name,us_per_call,derived")
    rows = []
    profile: dict = {}
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            for row in mod.run():
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.1f},{derived}",
                      flush=True)
                rows.append({**row, "module": name})
        except Exception as e:  # pragma: no cover
            failures += 1
            traceback.print_exc()
            print(f"{name},0,ERROR: {type(e).__name__}: {e}", flush=True)
            rows.append({"name": name, "us_per_call": 0.0, "module": name,
                         "derived": f"ERROR: {type(e).__name__}: {e}"})
        finally:
            wall = time.time() - t0
            profile[name] = wall
            rows.append({
                "name": f"{name}__module_wall",
                "us_per_call": wall * 1e6,
                "derived": "module wall-clock",
                "module": name,
            })
    if args.profile:
        total = sum(profile.values()) or 1.0
        print("--- profile (wall per stage) ---", file=sys.stderr)
        for name, wall in sorted(profile.items(), key=lambda kv: -kv[1]):
            print(f"{name:<24s} {wall:8.2f}s  {wall / total:6.1%}",
                  file=sys.stderr)
    if args.json:
        from benchmarks._env import env_metadata

        meta = env_metadata()
        try:
            from repro.analysis import ANALYSIS_VERSION
        except ImportError:  # src/ not on the path — provenance only
            ANALYSIS_VERSION = None
        meta["analysis"] = {"version": ANALYSIS_VERSION}
        if args.profile:
            # provenance only: compare.py drops the whole meta block, so
            # the breakdown can never become a gated (noisy) metric
            meta["profile"] = {
                "total_wall_s": sum(profile.values()),
                "stage_wall_s": {k: round(v, 4)
                                 for k, v in profile.items()},
            }
        payload = {
            "tier": args.tier,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "meta": meta,
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json} ({len(rows)} rows)", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
