#!/usr/bin/env python3
"""The correctness check's control, run as a whole cell, at its own size.

    python3 bench/control.py --workload fig2b-grid --seeds 1 2 3 --seconds 1

For each seed it runs the cell through the harness, exactly as
``bench/run.py`` does, but with the kind's control in the program's
place: for the co-simulator, the reference computed in float32, the
precision below the configuration's float64, answers every case and
round of each window call.  The control must come out not correct.
The benchmark's own runs never run this.  Prints each run's result line
and, last, a JSON object with every reading and whether any run came
out correct; exits non-zero if one did.
"""
import argparse
import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import env, harness

    cell = harness.load_cell(ROOT, args.workload)
    env.use_compile_cache(ROOT)
    import jax

    try:
        devs = harness.check_devices(jax, cell["cell"]["chips"])
    except harness.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    out = {}
    for seed in args.seeds:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            harness.execute(ROOT, cell, devs, seed, args.seconds, False,
                            time.time(), control=True)
        line = json.loads(buf.getvalue().strip().splitlines()[-1])
        print(json.dumps(line), flush=True)
        out[seed] = {"correct": line["correct"],
                     **{k: c["value"] for k, c in line["checks"].items()}}
    any_correct = any(r["correct"] for r in out.values())
    print(json.dumps({"workload": args.workload, "control": out,
                      "any_correct": any_correct}))
    return int(any_correct)


if __name__ == "__main__":
    sys.exit(main())
