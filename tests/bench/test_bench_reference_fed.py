"""The federated Mamba-2 program against its plain reference, on the CPU.

At a small size (d_model 64, 2 layers, chunk 16, 64 tokens a sequence)
and seeded weights, the program's forward logits and loss, gradients,
one AdamW step and one int8 FedAvg round agree with
:mod:`bench.reference_fed`, each within a tolerance whose reason is
given beside it; the reference with bfloat16 matrix products (the
control) misses every one of them.  A tiny ``fed`` cell runs through
the harness on four host devices, one pod each, in a subprocess:
``correct`` holds for the program and fails for the control.  The
``fed-pods4`` cell's files load through the harness's lookup by name.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench import reference_fed as ref  # noqa: E402
from bench.kinds import fed as fed_kind  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import SSMConfig  # noqa: E402
from repro.dist import stepfns  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim.optimizers import (  # noqa: E402
    OptimizerConfig,
    apply_updates,
    init_opt_state,
)
from repro.optim.schedules import warmup_cosine  # noqa: E402

CFG = get_config("mamba2-780m").replace(
    n_layers=2, d_model=64, vocab_size=128, dtype="float32",
    ssm=SSMConfig(d_state=16, expand=2, d_head=16, d_conv=4, chunk=16))
OPT = OptimizerConfig(name="adamw", lr=1e-2)
HP = {k: getattr(OPT, k) for k in ("b1", "b2", "eps", "weight_decay",
                                   "grad_clip")}

# Tolerances, each with its reason.  The program and the reference are
# float32 through different algorithms (a chunked scan against the
# quadratic dual form, other summation orders), so they differ by
# rounding alone; the control rounds every matrix operand to bfloat16.
TOL = {
    # relative max-abs gap of the logits: float32 rounding through two
    # layers reads ~1e-6; the bf16 control ~3e-2
    "logits": 1e-5,
    # relative gap of the mean loss: rounding reads ~1e-7, the control
    # ~1e-4
    "loss": 1e-6,
    # largest relative norm gap of a gradient leaf: ~1e-6, control ~3e-2
    "grads": 1e-5,
    # relative norm gap of one AdamW update (clipping, moments, bias
    # correction, decay of the matrices), elementwise float32 on the same
    # gradients; the control's update, on its own gradients, ~0.2
    "adamw": 1e-5,
    # the round's gap relative to the change it made: four-term float32
    # sums in another order, against a change ~1e-3 of the parameters,
    # read ~5e-6; the control ~0.5
    "round": 1e-4,
}


def params_of(seed: int):
    """Program-initialised weights with seeded per-head ``a_log``,
    ``dt_bias`` and ``d_skip`` (the initial zeros and ones would hide a
    fault in how ``A``, ``dt`` or ``D`` are applied)."""
    p = lm.init_params(jax.random.PRNGKey(seed), CFG)
    mix = p["units"]["b0"]["mixer"]
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    mix["a_log"] = 0.5 * jax.random.normal(k[0], mix["a_log"].shape)
    mix["dt_bias"] = 0.5 * jax.random.normal(k[1], mix["dt_bias"].shape)
    mix["d_skip"] = 1.0 + 0.1 * jax.random.normal(k[2], mix["d_skip"].shape)
    return p


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(2**31 + 7)
    tokens = rng.integers(0, CFG.vocab_size, (4, 64)).astype(np.int32)
    labels = rng.integers(0, CFG.vocab_size, (4, 64)).astype(np.int32)
    return params_of(0), tokens, labels


def rel_norm(got, want) -> float:
    """Largest ``|got - want| / |want|`` over the leaves (norms)."""
    return max(float(jnp.linalg.norm(jnp.ravel(a - b))
                     / jnp.linalg.norm(jnp.ravel(b)))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def gaps(case, low: bool) -> dict:
    params, tokens, labels = case
    batch = {"tokens": tokens, "labels": labels}
    lg_ref = ref.logits(params, tokens)
    lg = ref.logits(params, tokens, low=True) if low else \
        lm.forward_train(params, CFG, tokens)[0]
    loss_ref, g_ref = ref.loss_and_grads(params, tokens, labels, block=2)
    if low:
        loss, g = ref.loss_and_grads(params, tokens, labels, block=2,
                                     low=True)
    else:
        loss = float(lm.loss_fn(params, CFG, batch))
        g = jax.grad(lm.loss_fn)(params, CFG, batch)
    zeros = jax.tree.map(jnp.zeros_like, params)
    if low:
        # the control's step against the reference's, each on its own
        # gradients
        new, _, _ = ref.adamw(params, g, zeros, zeros, 1, OPT.lr, HP)
        want, _, _ = ref.adamw(params, g_ref, zeros, zeros, 1, OPT.lr, HP)
    else:
        # the program's optimizer against the reference's, on the same
        # gradients: AdamW's first step divides each gradient by its own
        # size, so rounding in a gradient near eps would swamp the test
        new, _, _ = apply_updates(params, g, init_opt_state(params, OPT),
                                  OPT, decay_mask=lm.weight_decay_mask(
                                      params))
        want, _, _ = ref.adamw(params, g, zeros, zeros, 1, OPT.lr, HP)
    step = jax.tree.map(lambda a, p: a - p, new, params)
    step_ref = jax.tree.map(lambda a, p: a - p, want, params)
    return {
        "logits": float(jnp.max(jnp.abs(lg - lg_ref))
                        / jnp.max(jnp.abs(lg_ref))),
        "loss": abs(loss - loss_ref) / abs(loss_ref),
        "grads": rel_norm(g, g_ref),
        "adamw": rel_norm(step, step_ref),
        "round": round_gap(low),
    }


def round_gap(low: bool) -> float:
    """Pod 0's copy after one int8 FedAvg round of four diverged pods,
    against the reference, relative to the change the round made."""
    base = params_of(0)
    pods = [base] + [
        jax.tree.map(lambda x, q=q: x + 1e-3 * jax.random.normal(
            jax.random.PRNGKey(q), x.shape), base) for q in range(1, 4)]
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    want = ref.fedavg_int8(pods, w)
    if low:
        got = ref.fedavg_int8(pods, w, low=True)
    else:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pods)
        out = jax.jit(stepfns.make_fed_round_step(CFG, "int8"))(
            stepfns.TrainState(params=stacked, opt=None), w).params
        got = jax.tree.map(lambda a: a[0], out)
    err = sum(float(jnp.sum((a - b) ** 2)) for a, b in
              zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    chg = sum(float(jnp.sum((b - x) ** 2)) for b, x in
              zip(jax.tree.leaves(want), jax.tree.leaves(base)))
    return (err / chg) ** 0.5


@pytest.fixture(scope="module")
def program_gaps(case):
    return gaps(case, low=False)


@pytest.fixture(scope="module")
def control_gaps(case):
    return gaps(case, low=True)


@pytest.mark.parametrize("what", list(TOL))
def test_program_matches_the_reference(program_gaps, what):
    assert program_gaps[what] <= TOL[what], program_gaps


@pytest.mark.parametrize("what", list(TOL))
def test_bf16_control_misses_the_tolerance(control_gaps, what):
    assert control_gaps[what] > TOL[what], control_gaps


def test_reference_ssm_is_the_token_recurrence():
    """The quadratic form equals ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t
    x_t``, ``y_t = C_t h_t + D x_t`` run token by token in float64."""
    rng = np.random.default_rng(5)
    b, S, H, P, N = 2, 12, 3, 4, 5
    x = rng.normal(size=(b, S, H, P))
    dt = np.log1p(np.exp(rng.normal(size=(b, S, H))))
    A = -np.exp(rng.normal(size=H))
    B, C = rng.normal(size=(b, S, N)), rng.normal(size=(b, S, N))
    D = rng.normal(size=H)
    h = np.zeros((b, H, P, N))
    want = np.zeros_like(x)
    for t in range(S):
        h = (h * np.exp(dt[:, t] * A)[..., None, None]
             + np.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t], x[:, t]))
        want[:, t] = np.einsum("bn,bhpn->bhp", C[:, t], h) + x[:, t] * D[
            :, None]
    got = ref.ssm(*(jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C, D)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)



def test_fed_cell_loads_by_name():
    cell = harness.load_cell(ROOT, "fed-pods4")
    assert cell["cell"]["chips"] == 4
    assert cell["config"]["kind"] == "fed"
    assert cell["config"]["reduced"] == ["n_layer"]
    assert cell["traffic"]["local_steps"] == 4
    assert {m["name"] for m in cell["per_layer"]} == {
        "fed_mfu_pct", "ssd_ms_per_round", "fedavg_ms_per_round",
        "device_idle_pct.sim", "compiles_in_window"}
    assert {m["name"] for m in cell["end_to_end"]} == {"sim_rounds_per_s",
                                                       "setup_s"}
    kind = harness.load_kind("fed")
    assert kind.RATE == "sim_rounds_per_s"
    assert set(harness.limits("fed")) == {"loss_rel_gap", "step_rel_gap",
                                          "round_rel_gap", "mismatches"}
    for m in cell["per_layer"]:
        if m["name"] != "compiles_in_window":
            assert harness.load_reader(m["name"])(
                harness.Run(1, None, 0, {})) is None
    cfg = fed_kind.model_config(cell["config"])
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (8, 1536, 50280)
    assert (cfg.ssm.d_state, cfg.ssm.expand, cfg.ssm.d_head, cfg.ssm.d_conv,
            cfg.ssm.chunk) == (128, 2, 64, 4, 128)


def test_train_flops_count_the_matmuls():
    """Forward multiply-adds of a 1-layer model by hand: in and out
    projections, conv, the chunked scan's four contractions, the head."""
    D, V, N, E, P, K, Q = 8, 10, 2, 2, 4, 3, 4
    d_in, H = E * D, E * D // P
    fwd = (2 * D * (2 * d_in + 2 * N + H) + 2 * d_in * D
           + 2 * K * (d_in + 2 * N)
           + 2 * Q * N + 2 * Q * H * P + 4 * N * H * P + 2 * D * V)
    assert ref.train_flops_per_token(D, 1, V, N, E, P, K, 6, Q) == 3 * fwd


@pytest.mark.parametrize("count", [0, 1, 7, 20, 21, 5000, 10000, 12000])
def test_reference_schedule_is_the_trainers(count):
    want = float(warmup_cosine(3e-4, 20, 10000)(jnp.asarray(count)))
    assert ref.warmup_cosine(count, 3e-4, 20, 10000) == pytest.approx(
        want, rel=1e-6, abs=1e-12)


def test_scope_seconds_reads_the_current_cells_programs():
    class Summary:
        ops = {"fusion.1 (fusion)": 2.0, "fusion.2 (fusion)": 3.0,
               "while.3 (while)": 7.0, "fusion.4 (fusion)": 5.0,
               "all-reduce.5 (all-reduce)": 0.5}

    class Cell:
        fed = object()

        @staticmethod
        def scope_tables():
            return {"jit_fed_step": {"fusion.1": "ssd_scan",
                                     "while.3": "ssd_scan",
                                     "fusion.2": "ssd_proj",
                                     "fusion.4": "ssd_scan"},
                    "jit_fed_round": {"fusion.4": "fedavg",
                                      "all-reduce.5": "fedavg"}}

    saved = list(fed_kind.CURRENT)
    fed_kind.CURRENT[:] = [Cell]
    try:
        # fusion.4 is named in both programs and the loop holds its body
        assert fed_kind.scope_seconds(Summary, ["jit_fed_step"],
                                      "ssd_scan") == 2.0
        assert fed_kind.scope_seconds(Summary, ["jit_fed_round"]) == 0.5
    finally:
        fed_kind.CURRENT[:] = saved


HARNESS_RUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import contextlib, io, json, sys
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness
    from bench.kinds import fed

    cell = harness.load_cell(ROOT, "fed-pods4")
    cell["config"] = dict(cell["config"], model=dict(
        cell["config"]["model"], n_layer=2, d_model=64, vocab_size=128,
        d_state=16, headdim=16, chunk_size=16))
    cell["traffic"] = dict(cell["traffic"], per_pod_batch=4, seq_len=64,
                           token_pool=40_000, ref_block=2)
    out = {}
    for control in (False, True):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = harness.execute(ROOT, cell, jax.devices()[:4],
                                 2**31 + 11, 1e-3, False, 0.0,
                                 control=control)
        line = json.loads(buf.getvalue().strip().splitlines()[-1])
        out["control" if control else "program"] = dict(line, rc=rc)
        if not control:
            tables = fed.CURRENT[0].scope_tables()
            out["scopes"] = {m: sorted(set(t.values()) - {""})
                             for m, t in tables.items()}
    print("FED_RUN " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def harness_runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = f"ROOT = {ROOT!r}\n" + HARNESS_RUN
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("FED_RUN ")]
    assert lines, out.stdout[-3000:] + out.stderr[-6000:]
    return json.loads(lines[-1].split(" ", 1)[1])


def test_tiny_fed_cell_is_correct_through_the_harness(harness_runs):
    line = harness_runs["program"]
    assert line["rc"] == 0
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "sim_rounds_per_s"}
    assert line["device"]["count"] == 4
    assert line["checks"]["mismatches"]["value"] == 0
    assert line["checks"]["loss_rel_gap"]["value"] < 1e-6
    assert line["checks"]["step_rel_gap"]["value"] < 1e-4
    assert line["checks"]["round_rel_gap"]["value"] < 1e-4


def test_fed_readers_find_the_scopes_of_both_programs(harness_runs):
    """What ``ssd_ms_per_round`` and ``fedavg_ms_per_round`` read: the
    compiled step holds the SSD and optimizer scopes, the round the
    FedAvg scope."""
    scopes = harness_runs["scopes"]
    assert set(scopes) == set(fed_kind.PROGRAMS)
    assert {"ssd_scan", "ssd_proj", "optimizer"} <= set(scopes["jit_fed_step"])
    assert scopes["jit_fed_round"] == ["fedavg"]


def test_bf16_control_is_not_correct_through_the_harness(harness_runs):
    line = harness_runs["control"]
    assert line["rc"] == 0
    assert line["correct"] is False
    assert line["checks"]["step_rel_gap"]["value"] > 1e-2
    assert line["checks"]["round_rel_gap"]["value"] > 1e-2
