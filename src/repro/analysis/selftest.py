"""``--self-test``: the analysis pass checks itself before checking code.

Mirrors ``benchmarks/compare.py --self-test`` (the synthetic-regression
probe for the benchmark gate): for every rule, a minimal *violating*
snippet must fire and its *fixed twin* must stay silent, and a
synthetically corrupted stream-key constant must trip RPA006.  A
checker whose positive fixture stops firing has silently lost its
teeth — that must fail CI exactly like a real regression would.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from repro.analysis import registry
from repro.analysis.core import ModuleInfo, all_checkers, run_checkers

# (code, violating-source, clean-twin-source, synthetic path)
FIXTURES: List[Tuple[str, str, str, str]] = [
    (
        "RPA001",
        "import numpy as np\n"
        "def jitter(n):\n"
        "    return np.random.poisson(3.0, n)\n",
        "import numpy as np\n"
        "def jitter(n, seed):\n"
        "    return np.random.default_rng(seed).poisson(3.0, n)\n",
        "repro/net/_fixture_rng.py",
    ),
    (
        "RPA002",
        "import time\n"
        "def stamp(rows):\n"
        "    return [(time.time(), r) for r in rows]\n",
        "def stamp(rows, now_s):\n"
        "    return [(now_s, r) for r in rows]\n",
        "repro/net/_fixture_clock.py",
    ),
    (
        "RPA003",
        "def total(ids):\n"
        "    out = 0.0\n"
        "    for i in set(ids):\n"
        "        out += 1.0 / (1 + i)\n"
        "    return out\n",
        "def total(ids):\n"
        "    out = 0.0\n"
        "    for i in sorted(set(ids)):\n"
        "        out += 1.0 / (1 + i)\n"
        "    return out\n",
        "repro/net/_fixture_set.py",
    ),
    (
        "RPA004",
        "import jax\n"
        "jax.config.update(\"jax_enable_x64\", True)\n",
        "import jax\n"
        "def run(fn):\n"
        "    with jax.enable_x64(True):\n"
        "        return fn()\n",
        "repro/net/_fixture_x64.py",
    ),
    (
        "RPA005",
        "import jax.numpy as jnp\n"
        "def scale_ref(x, lim):\n"
        "    if x > lim:\n"
        "        return float(x)\n"
        "    return jnp.minimum(x, lim)\n",
        "import jax.numpy as jnp\n"
        "def scale_ref(x, lim):\n"
        "    return jnp.where(x > lim, x, jnp.minimum(x, lim))\n",
        "repro/kernels/_fixture_tracer.py",
    ),
    (
        "RPA007",
        "def simulate(state, collector):\n"
        "    if collector is not None:\n"
        "        collector.event(\"round\")\n"
        "        state = state + 1\n"
        "    return state\n",
        "def simulate(state, collector):\n"
        "    if collector is not None:\n"
        "        collector.event(\"round\", state=state)\n"
        "    return state + 1\n",
        "repro/net/_fixture_collector.py",
    ),
]


def _mod(path: str, source: str) -> ModuleInfo:
    return ModuleInfo(path=path, tree=ast.parse(source), source=source)


def run_self_test(verbose: bool = True) -> int:
    """0 on success; prints one line per probe like compare.py's."""
    failures = 0

    def report(ok: bool, label: str) -> None:
        nonlocal failures
        if not ok:
            failures += 1
        if verbose or not ok:
            print(f"self-test {'ok  ' if ok else 'FAIL'}: {label}")

    for code, bad_src, good_src, path in FIXTURES:
        checkers = all_checkers(select=[code])
        bad = run_checkers([_mod(path, bad_src)], checkers)
        good = run_checkers([_mod(path, good_src)], checkers)
        report(
            any(f.code == code for f in bad),
            f"{code} fires on its violating fixture",
        )
        report(
            not good,
            f"{code} stays silent on the fixed twin"
            + (f" (got: {good[0].message})" if good else ""),
        )

    # RPA006: corrupt one Weyl constant of a synthetic two-module anchor
    # set so the duplicate-detection path is exercised end to end.
    ref_src = (
        "KEY_WEYL_0 = 0x9E3779B9\n"
        "KEY_WEYL_1 = 0x85EBCA6B\n"
        "_C240 = 0x1BD11BDA\n"
    )
    fault_ok = (
        "_CLASS_WEYL_0 = 0x9E3779B1\n"
        "_CLASS_WEYL_1 = 0x85EBCA77\n"
        "_CASE_WEYL = 0x6C8E9CF5\n"
        "_PON_WEYL_0 = 0xCC9E2D51\n"
        "_PON_WEYL_1 = 0x1B873593\n"
        "_JOB_WEYL_0 = 0xC2B2AE35\n"
        "_JOB_WEYL_1 = 0x27D4EB2F\n"
    )
    # corruption: the fault-class constant collides with KEY_WEYL_0
    fault_bad = fault_ok.replace("0x9E3779B1", "0x9E3779B9")
    checkers = all_checkers(select=["RPA006"])
    clean = run_checkers(
        [
            _mod("repro/kernels/traffic/ref.py", ref_src),
            _mod("repro/faults/streams.py", fault_ok),
        ],
        checkers,
    )
    corrupt = run_checkers(
        [
            _mod("repro/kernels/traffic/ref.py", ref_src),
            _mod("repro/faults/streams.py", fault_bad),
        ],
        checkers,
    )
    report(not clean, "RPA006 passes a disjoint synthetic registry")
    report(
        any("duplicate" in f.message for f in corrupt),
        "RPA006 flags a corrupted (colliding) stream-key constant",
    )
    even = run_checkers(
        [
            _mod("repro/kernels/traffic/ref.py", ref_src),
            _mod(
                "repro/faults/streams.py",
                fault_ok.replace("0x6C8E9CF5", "0x6C8E9CF4"),
            ),
        ],
        checkers,
    )
    report(
        any("even" in f.message for f in even),
        "RPA006 flags an even Weyl increment",
    )

    # RPA008: a kernel package missing its oracle must be flagged
    triple: Dict[str, str] = {
        "repro/kernels/fake/__init__.py": "",
        "repro/kernels/fake/kernel.py": (
            "def op_fwd(x, block):\n    return x\n"
        ),
        "repro/kernels/fake/ops.py": "def op(x, block):\n    return x\n",
    }
    checkers = all_checkers(select=["RPA008"])
    missing = run_checkers(
        [_mod(p, s) for p, s in triple.items()], checkers
    )
    full = run_checkers(
        [_mod(p, s) for p, s in triple.items()]
        + [
            _mod(
                "repro/kernels/fake/ref.py",
                "def op_ref(x, block):\n    return x\n",
            )
        ],
        checkers,
    )
    report(
        any("missing" in f.message for f in missing),
        "RPA008 flags a kernel package without ref.py",
    )
    report(not full, "RPA008 passes a complete conforming triple")

    # registry sanity: the validator itself must reject a duplicate
    consts = [
        registry.StreamConstant("a.py", "A_WEYL", 0x9E3779B9, 1),
        registry.StreamConstant("b.py", "B_WEYL", 0x9E3779B9, 1),
    ]
    report(
        bool(registry.validate_constants(consts)),
        "registry validator rejects duplicated constants",
    )

    if failures:
        print(f"self-test: {failures} probe(s) FAILED")
        return 1
    print("self-test: all probes passed")
    return 0
