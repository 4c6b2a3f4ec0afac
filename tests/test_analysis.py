"""Tests for repro.analysis — the invariant-aware static analysis pass.

Layout mirrors the rule list in DESIGN.md §13: for every RPA0xx code a
violating fixture must fire and its fixed twin must stay silent; the
stream-key disjointness rule is additionally exercised end to end by
corrupting one Weyl constant in a synthetic repro-shaped tree; and the
real package must come out clean modulo the checked-in baseline.
"""

from __future__ import annotations

import ast
import json
import os

import pytest

from repro.analysis import ANALYSIS_VERSION
from repro.analysis.baseline import apply_baseline, load_baseline
from repro.analysis.cli import main
from repro.analysis.core import ModuleInfo, all_checkers, run_checkers
from repro.analysis.selftest import run_self_test

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _findings(code, path, source):
    mod = ModuleInfo(path=path, tree=ast.parse(source), source=source)
    return run_checkers([mod], all_checkers(select=[code]))


def _assert_fires(code, path, source):
    found = _findings(code, path, source)
    assert any(f.code == code for f in found), f"{code} did not fire"
    return found


def _assert_silent(code, path, source):
    found = _findings(code, path, source)
    assert not found, f"{code} fired unexpectedly: {found[0].message}"


# ---------------------------------------------------------------------------
# RPA001 — host RNG in engine paths


def test_rpa001_fires_on_unseeded_numpy_rng():
    _assert_fires(
        "RPA001",
        "repro/net/x.py",
        "import numpy as np\n"
        "def jitter(n):\n"
        "    return np.random.poisson(3.0, n)\n",
    )


def test_rpa001_fires_on_stdlib_random():
    _assert_fires(
        "RPA001",
        "repro/kernels/x.py",
        "import random\n"
        "def pick(xs):\n"
        "    return random.choice(xs)\n",
    )


def test_rpa001_silent_on_seeded_generator():
    _assert_silent(
        "RPA001",
        "repro/net/x.py",
        "import numpy as np\n"
        "def jitter(n, seed):\n"
        "    return np.random.default_rng(seed).poisson(3.0, n)\n",
    )


def test_rpa001_scoped_to_engine_packages():
    # the same host RNG outside net/kernels/faults is out of scope
    _assert_silent(
        "RPA001",
        "repro/obs/x.py",
        "import random\n"
        "def pick(xs):\n"
        "    return random.choice(xs)\n",
    )


# ---------------------------------------------------------------------------
# RPA002 — wall-clock reads


def test_rpa002_fires_on_time_time():
    _assert_fires(
        "RPA002",
        "repro/net/x.py",
        "import time\n"
        "def stamp(rows):\n"
        "    return [(time.time(), r) for r in rows]\n",
    )


def test_rpa002_silent_when_time_is_a_parameter():
    _assert_silent(
        "RPA002",
        "repro/net/x.py",
        "def stamp(rows, now_s):\n"
        "    return [(now_s, r) for r in rows]\n",
    )


def test_rpa002_respects_noqa():
    _assert_silent(
        "RPA002",
        "repro/net/x.py",
        "import time\n"
        "def stamp():\n"
        "    return time.time()  # noqa: RPA002\n",
    )


# ---------------------------------------------------------------------------
# RPA003 — unordered iteration


def test_rpa003_fires_on_set_iteration():
    _assert_fires(
        "RPA003",
        "repro/net/x.py",
        "def total(ids):\n"
        "    out = 0.0\n"
        "    for i in set(ids):\n"
        "        out += 1.0 / (1 + i)\n"
        "    return out\n",
    )


def test_rpa003_fires_on_unsorted_listdir():
    _assert_fires(
        "RPA003",
        "repro/faults/x.py",
        "import os\n"
        "def cases(d):\n"
        "    return [f for f in os.listdir(d)]\n",
    )


def test_rpa003_silent_when_sorted():
    _assert_silent(
        "RPA003",
        "repro/net/x.py",
        "def total(ids):\n"
        "    out = 0.0\n"
        "    for i in sorted(set(ids)):\n"
        "        out += 1.0 / (1 + i)\n"
        "    return out\n",
    )


def test_rpa003_silent_on_order_free_reductions():
    _assert_silent(
        "RPA003",
        "repro/net/x.py",
        "def n_unique(ids):\n"
        "    return len(set(ids))\n",
    )


# ---------------------------------------------------------------------------
# RPA004 — ambient x64 flips


def test_rpa004_fires_on_ambient_config_update():
    _assert_fires(
        "RPA004",
        "repro/util.py",
        "import jax\n"
        "jax.config.update(\"jax_enable_x64\", True)\n",
    )


def test_rpa004_fires_on_env_var_store():
    _assert_fires(
        "RPA004",
        "repro/util.py",
        "import os\n"
        "os.environ[\"JAX_ENABLE_X64\"] = \"1\"\n",
    )


def test_rpa004_silent_on_scoped_context():
    _assert_silent(
        "RPA004",
        "repro/util.py",
        "import jax\n"
        "def run(fn):\n"
        "    with jax.enable_x64(True):\n"
        "        return fn()\n",
    )


# ---------------------------------------------------------------------------
# RPA005 — tracer purity


def test_rpa005_fires_on_branch_and_float_in_traced_ref():
    found = _assert_fires(
        "RPA005",
        "repro/kernels/x/ref.py",
        "import jax.numpy as jnp\n"
        "def scale_ref(x, lim):\n"
        "    if x > lim:\n"
        "        return float(x)\n"
        "    return jnp.minimum(x, lim)\n",
    )
    assert len(found) >= 2  # both the branch and the float() sync


def test_rpa005_fires_on_item_in_jit_callee():
    _assert_fires(
        "RPA005",
        "repro/kernels/x/ops.py",
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def _step(c):\n"
        "    return jnp.float32(c.item())\n"
        "run = jax.jit(_step)\n",
    )


def test_rpa005_silent_on_lax_cond():
    _assert_silent(
        "RPA005",
        "repro/kernels/x/ref.py",
        "import jax.numpy as jnp\n"
        "def scale_ref(x, lim):\n"
        "    return jnp.where(x > lim, x, jnp.minimum(x, lim))\n",
    )


def test_rpa005_annotated_static_param_is_not_a_tracer():
    # regression: `n: int` kw-only config params may drive Python
    # control flow even when the name also appears (via a closure)
    # inside lax/jnp call arguments — ponsim's sample_window_ref shape
    _assert_silent(
        "RPA005",
        "repro/kernels/x/ref.py",
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def win_ref(x, *, n_draws: int):\n"
        "    j_half = max(1, n_draws // 2)\n"
        "    if j_half < n_draws:\n"
        "        x = x * 2\n"
        "    return lax.cond(\n"
        "        jnp.sum(x) > 0,\n"
        "        lambda p: p * n_draws,\n"
        "        lambda p: p,\n"
        "        x,\n"
        "    )\n",
    )


def test_rpa005_static_shape_branch_is_fine():
    _assert_silent(
        "RPA005",
        "repro/kernels/x/ref.py",
        "import jax.numpy as jnp\n"
        "def pad_ref(x):\n"
        "    if x.ndim == 1:\n"
        "        x = x[None, :]\n"
        "    return jnp.cumsum(x, axis=-1)\n",
    )


# ---------------------------------------------------------------------------
# RPA007 — collector purity


def test_rpa007_fires_on_unguarded_collector_use():
    _assert_fires(
        "RPA007",
        "repro/net/x.py",
        "def simulate(state, collector=None):\n"
        "    collector.event(\"round\")\n"
        "    return state + 1\n",
    )


def test_rpa007_fires_on_engine_write_in_guard():
    _assert_fires(
        "RPA007",
        "repro/net/x.py",
        "def simulate(state, collector=None):\n"
        "    if collector is not None:\n"
        "        collector.event(\"round\")\n"
        "        state = state + 1\n"
        "    return state\n",
    )


def test_rpa007_silent_on_guarded_readonly_obs():
    _assert_silent(
        "RPA007",
        "repro/net/x.py",
        "def simulate(state, collector=None):\n"
        "    if collector is not None:\n"
        "        collector.event(\"round\", state=state)\n"
        "    return state + 1\n",
    )


def test_rpa007_silent_on_early_none_return():
    _assert_silent(
        "RPA007",
        "repro/net/x.py",
        "def record(collector, rows):\n"
        "    if collector is None or not rows:\n"
        "        return\n"
        "    collector.event(\"rows\", n=len(rows))\n",
    )


def test_rpa007_required_collector_is_out_of_scope():
    # regression: a helper whose collector argument is mandatory (no
    # None default, never None-tested) is not an optional-obs entry
    # point — obs/export.py's MetricsReport.from_collector shape
    _assert_silent(
        "RPA007",
        "repro/obs/x.py",
        "def export(collector):\n"
        "    rows = collector.rows()\n"
        "    return {\"n\": len(rows), \"meta\": collector.meta}\n",
    )


def test_rpa007_passing_collector_through_is_not_an_alias():
    # regression: `timeline = simulate(..., collector=collector)` must
    # not mark `timeline` as a collector alias (launch/train.py shape)
    _assert_silent(
        "RPA007",
        "repro/net/x.py",
        "def run(cfg, collector=None):\n"
        "    timeline = simulate(cfg, collector=collector)\n"
        "    total = timeline.sum()\n"
        "    return total\n",
    )


# ---------------------------------------------------------------------------
# RPA006 — stream-key disjointness (synthetic repro-shaped tree)

_REF_SRC = (
    "KEY_WEYL_0 = 0x9E3779B9\n"
    "KEY_WEYL_1 = 0x85EBCA6B\n"
    "_C240 = 0x1BD11BDA\n"
)
_OPS_SRC = (
    "_PON_WEYL_0 = 0xCC9E2D51\n"
    "_PON_WEYL_1 = 0x1B873593\n"
    "_JOB_WEYL_0 = 0xC2B2AE35\n"
    "_JOB_WEYL_1 = 0x27D4EB2F\n"
)
_STREAMS_SRC = (
    "_CLASS_WEYL_0 = 0x9E3779B1\n"
    "_CLASS_WEYL_1 = 0x85EBCA77\n"
    "_CASE_WEYL = 0x6C8E9CF5\n"
)


def _write_tree(tmp_path, streams_src):
    pkg = tmp_path / "repro"
    (pkg / "kernels" / "traffic").mkdir(parents=True)
    (pkg / "faults").mkdir()
    (pkg / "kernels" / "traffic" / "ref.py").write_text(_REF_SRC)
    (pkg / "kernels" / "traffic" / "ops.py").write_text(_OPS_SRC)
    (pkg / "faults" / "streams.py").write_text(streams_src)
    return str(pkg)


def test_rpa006_clean_registry_passes(tmp_path):
    root = _write_tree(tmp_path, _STREAMS_SRC)
    assert main(["--select", "RPA006", root]) == 0


def test_rpa006_corrupted_weyl_constant_fails(tmp_path, capsys):
    # corrupt one fault-class constant into the traffic sampler's
    # KEY_WEYL_0 — exactly the latent collision this PR fixed for real
    bad = _STREAMS_SRC.replace("0x9E3779B1", "0x9E3779B9")
    root = _write_tree(tmp_path, bad)
    assert main(["--select", "RPA006", root]) == 1
    out = capsys.readouterr().out
    assert "RPA006" in out and "duplicate" in out


def test_rpa006_even_weyl_increment_fails(tmp_path, capsys):
    bad = _STREAMS_SRC.replace("0x6C8E9CF5", "0x6C8E9CF4")
    root = _write_tree(tmp_path, bad)
    assert main(["--select", "RPA006", root]) == 1
    assert "even" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# RPA008 — kernel-triple conformance

_TRIPLE = {
    "repro/kernels/fake/__init__.py": "",
    "repro/kernels/fake/kernel.py": (
        "def op_fwd(x, block):\n    return x\n"
    ),
    "repro/kernels/fake/ref.py": "def op_ref(x, block):\n    return x\n",
    "repro/kernels/fake/ops.py": "def op(x, block):\n    return x\n",
}


def _triple_findings(overrides):
    files = dict(_TRIPLE)
    files.update(overrides)
    mods = [
        ModuleInfo(path=p, tree=ast.parse(s), source=s)
        for p, s in sorted(files.items())
        if s is not None
    ]
    return run_checkers(mods, all_checkers(select=["RPA008"]))


def test_rpa008_complete_triple_passes():
    assert not _triple_findings({})


def test_rpa008_missing_ref_fires():
    found = _triple_findings({"repro/kernels/fake/ref.py": None})
    assert any("missing" in f.message for f in found)


def test_rpa008_ref_importing_kernel_fires():
    found = _triple_findings(
        {
            "repro/kernels/fake/ref.py": (
                "from repro.kernels.fake import kernel\n"
                "def op_ref(x, block):\n    return x\n"
            )
        }
    )
    assert any("independent witness" in f.message for f in found)


def test_rpa008_transposed_positional_params_fire():
    found = _triple_findings(
        {
            "repro/kernels/fake/ref.py": (
                "def op_ref(block, x):\n    return x\n"
            )
        }
    )
    assert found


def test_rpa008_kwonly_params_are_order_free():
    # regression: traffic's sample_arrival_bits_ref takes its config as
    # keyword-only args — their order vs the dispatch is irrelevant
    assert not _triple_findings(
        {
            "repro/kernels/fake/ops.py": (
                "def op(x, *, block, width):\n    return x\n"
            ),
            "repro/kernels/fake/ref.py": (
                "def op_ref(x, *, width, block):\n    return x\n"
            ),
        }
    )


# ---------------------------------------------------------------------------
# baseline mechanics


def test_baseline_suppresses_and_reports_stale(tmp_path):
    src = (
        "import time\n"
        "def stamp():\n"
        "    return time.time()\n"
    )
    mod = ModuleInfo(
        path="repro/net/x.py", tree=ast.parse(src), source=src
    )
    findings = run_checkers([mod], all_checkers(select=["RPA002"]))
    assert findings
    bl = tmp_path / "bl.json"
    bl.write_text(
        json.dumps(
            {
                "entries": [
                    {
                        "code": "RPA002",
                        "path": "repro/net/x.py",
                        "symbol": "*",
                        "note": "test exemption",
                    },
                    {
                        "code": "RPA001",
                        "path": "repro/net/gone.py",
                        "symbol": "*",
                        "note": "stale on purpose",
                    },
                ]
            }
        )
    )
    new, suppressed, stale = apply_baseline(findings, load_baseline(str(bl)))
    assert not new and suppressed
    assert [e.path for e in stale] == ["repro/net/gone.py"]


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "bl.json"
    bl.write_text(
        json.dumps(
            {
                "entries": [
                    {
                        "code": "RPA002",
                        "path": "x.py",
                        "symbol": "*",
                        "note": "   ",
                    }
                ]
            }
        )
    )
    with pytest.raises(ValueError, match="empty note"):
        load_baseline(str(bl))


# ---------------------------------------------------------------------------
# CLI behavior


def test_cli_json_format_and_artifact(tmp_path, capsys):
    pkg = tmp_path / "repro" / "net"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text(
        "import time\n"
        "def stamp():\n"
        "    return time.time()\n"
    )
    out_path = tmp_path / "report.json"
    rc = main(
        [
            "--format", "json",
            "--output", str(out_path),
            str(tmp_path / "repro"),
        ]
    )
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["analysis_version"] == ANALYSIS_VERSION
    assert payload["summary"]["findings"] >= 1
    assert any(f["code"] == "RPA002" for f in payload["findings"])
    on_disk = json.loads(out_path.read_text())
    assert on_disk["summary"] == payload["summary"]


def test_cli_wiring_errors_exit_2(tmp_path):
    assert main([str(tmp_path / "does-not-exist")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main([str(empty)]) == 2


def test_cli_unknown_select_exits_2():
    assert main(["--select", "RPA999", "src/repro"]) == 2


def test_self_test_passes():
    assert run_self_test(verbose=False) == 0


# ---------------------------------------------------------------------------
# the real package is clean modulo the checked-in baseline


def test_self_run_on_repro_is_clean():
    rc = main(
        [
            "--baseline", os.path.join(REPO_ROOT, "analysis-baseline.json"),
            os.path.join(REPO_ROOT, "src", "repro"),
        ]
    )
    assert rc == 0
