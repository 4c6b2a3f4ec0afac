"""Tracing of the federated pod programs from inside.

* the local step and the FedAvg round are the modules ``jit_fed_step``
  and ``jit_fed_round``; the step's ops carry the
  ``ssd_proj``/``ssd_scan``/``optimizer`` scopes, and the round's the
  ``fedavg`` scope with ``fed.quantise`` and ``fed.allreduce`` inside;
* ``FedPods`` records the ``fed.step`` and ``fed.round`` spans into a
  collector, counts local steps and rounds, and changes no result bit
  when a collector is given;
* the spans reach a ``jax.profiler`` trace with no collector at all;
* ``FedPods``' round is bitwise the plain jitted round, pod-sharded;
* a float32 train step asks for float32 matmuls, and AdamW decays no
  stacked per-layer vector.

The pod programs run on four host devices in a subprocess (the main
test process keeps one device).
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

from repro.configs import get_config
from repro.dist import stepfns
from repro.models import lm
from repro.optim.optimizers import OptimizerConfig, apply_updates

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import glob, json, sys, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.data import TokenBatcher, lm_tokens
    from repro.dist import stepfns
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import FedPods
    from repro.obs import Collector, SpanTracer
    from repro.obs.scopes import hlo_scopes
    from repro.optim.optimizers import OptimizerConfig

    SCOPES = ("ssd_scan", "ssd_proj", "optimizer", "fedavg")
    ROUND_PARTS = ("fed.quantise", "fed.allreduce")
    cfg = get_config("mamba2-780m", smoke=True)
    mesh = make_host_mesh(model_parallel=1, pods=4)
    tokens = lm_tokens(20_000, cfg.vocab_size, seed=3)

    def run(collector):
        fed = FedPods(cfg, OptimizerConfig(name="adamw", lr=1e-2), mesh, 4,
                      collector=collector)
        iters = [iter(TokenBatcher(tokens, 2, 32, seed=q, pod_index=q,
                                   n_pods=4)) for q in range(4)]
        with mesh:
            state = fed.init_state(jax.random.PRNGKey(0))
            losses = []
            for _ in range(2):
                state, m = fed.local_step(
                    state, fed.batch([next(g) for g in iters]))
                losses.append(np.asarray(m["loss"]).tolist())
            pre = state
            state = fed.round_step("int8")(state, jnp.arange(1.0, 5.0))
            one = jax.jit(stepfns.make_fed_round_step(cfg, "int8"))(
                pre, jnp.arange(1.0, 5.0))
        return fed, pre, state, one, losses

    out = {}
    col = Collector(tracer=SpanTracer())
    fed, pre, state, one, losses = run(col)
    out["events"] = [(e["name"], e["ts"], e["dur"])
                     for e in col.tracer.events]
    out["counters"] = fed.counters()
    _, _, plain, _, plain_losses = run(None)
    leaves = lambda t: [np.asarray(x) for x in jax.tree.leaves(t)]
    out["bitwise_collector"] = losses == plain_losses and all(
        np.array_equal(a, b) for a, b in zip(leaves(state), leaves(plain)))
    out["bitwise_plain_round"] = all(
        np.array_equal(a, b) for a, b in zip(leaves(state), leaves(one)))
    out["pods_split"] = all(
        len({s.device for s in x.addressable_shards}) == 4
        for x in jax.tree.leaves(state))

    batch = {k: jax.ShapeDtypeStruct((4, 2, 32), jnp.int32,
                                     sharding=fed.batch_sharding)
             for k in ("tokens", "labels")}
    fed_round = jax.jit(stepfns.make_fed_round_step(cfg, "int8"))
    with mesh:
        texts = {
            "step": fed.step.lower(pre, batch).compile().as_text(),
            "round": fed_round.lower(pre, jnp.ones(4)).compile().as_text(),
        }
    out["modules"] = {k: t.split("\\n", 1)[0].split()[1].rstrip(",")
                      for k, t in texts.items()}
    out["scopes"] = {k: sorted(set(hlo_scopes(t, SCOPES).values()))
                     for k, t in texts.items()}
    out["round_parts"] = sorted(set(
        hlo_scopes(texts["round"], ROUND_PARTS).values()))

    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    run(None)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    prof = jax.profiler.ProfileData.from_file(path[0])
    out["profiler_names"] = sorted(
        {ev.name for p in prof.planes for ln in p.lines for ev in ln.events
         if ev.name.startswith("fed.")})
    print("FED_TRACE " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def traced():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("FED_TRACE ")]
    assert lines, out.stdout[-3000:] + out.stderr[-6000:]
    return json.loads(lines[-1].split(" ", 1)[1])


def test_fed_programs_are_named_modules(traced):
    assert traced["modules"] == {"step": "jit_fed_step",
                                 "round": "jit_fed_round"}


def test_fed_programs_carry_their_scopes(traced):
    assert traced["scopes"] == {"step": ["optimizer", "ssd_proj",
                                         "ssd_scan"],
                                "round": ["fedavg"]}
    assert traced["round_parts"] == ["fed.allreduce", "fed.quantise"]


def test_collector_records_the_fed_spans_in_order(traced):
    events = sorted(traced["events"], key=lambda e: e[1])
    assert [e[0] for e in events] == ["fed.step", "fed.step", "fed.round"]
    for a, b in zip(events, events[1:]):
        assert a[1] + a[2] <= b[1]


def test_counters_count_local_steps_and_rounds(traced):
    assert traced["counters"] == {"fed_local_steps": 2, "fed_rounds": 1}


def test_collector_is_bitwise_free(traced):
    assert traced["bitwise_collector"] is True


def test_fed_round_is_the_plain_round(traced):
    assert traced["bitwise_plain_round"] is True
    assert traced["pods_split"] is True


def test_fed_spans_reach_the_profiler_without_a_collector(traced):
    assert traced["profiler_names"] == ["fed.round", "fed.step"]


def _dot_lines(cfg):
    """The dot_general lines of the lowered train step."""
    opt = OptimizerConfig(name="adamw")
    state = stepfns.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    text = jax.jit(stepfns.make_train_step(cfg, opt)).lower(
        state, batch).as_text()
    return [ln for ln in text.splitlines() if "dot_general" in ln]


def test_float32_train_step_asks_for_float32_matmuls():
    cfg = get_config("mamba2-780m", smoke=True)
    highest = _dot_lines(cfg.replace(dtype="float32"))
    default = _dot_lines(cfg.replace(dtype="bfloat16"))
    assert highest and len(highest) == len(default)
    assert all("HIGHEST" in ln for ln in highest)
    assert not any("HIGHEST" in ln for ln in default)


def test_adamw_decays_matrices_and_no_stacked_vector():
    cfg = get_config("mamba2-780m", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda p: p + 1.0, params)   # no zero leaves
    opt = OptimizerConfig(name="adamw", lr=0.1, weight_decay=0.5)
    from repro.optim.optimizers import init_opt_state

    zeros = jax.tree.map(jnp.zeros_like, params)
    new, _, _ = apply_updates(params, zeros, init_opt_state(params, opt),
                              opt, decay_mask=lm.weight_decay_mask(params))
    mix = new["units"]["b0"]["mixer"]
    old = params["units"]["b0"]["mixer"]
    for name in ("a_log", "dt_bias", "d_skip", "gate_norm_scale"):
        np.testing.assert_array_equal(mix[name], old[name])
    np.testing.assert_array_equal(new["units"]["b0"]["mix_norm"]["scale"],
                                  params["units"]["b0"]["mix_norm"]["scale"])
    for name in ("in_proj", "out_proj", "conv_w"):
        assert not np.allclose(mix[name], old[name])
    assert not np.allclose(new["embed"], params["embed"])
