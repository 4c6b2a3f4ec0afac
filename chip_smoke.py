#!/usr/bin/env python3
"""Smoke run of the PON co-simulator's main path on a TPU.

    python chip_smoke.py                # one chip: simulate(SweepSpec)
    python chip_smoke.py --four-chips   # four chips: federated rounds

One chip drives ``repro.net.simulate(SweepSpec)`` at the paper's sizes:

1. the device samplers (the Pallas traffic kernel and the device
   engine's fused window sampler) against the host numpy stream at 128
   ONUs and load 0.8, bit for bit;
2. the Fig. 2b operating point (128 ONUs at 10 Gb/s, fcfs, load 0.8,
   seed 1, 12 clients of 26.416 Mb): exactly ``5.058100000000024`` s on
   the default numpy engine, whose traffic the compiled Pallas sampler
   draws on a TPU, and within rtol 1e-6 of it on ``backend="jit"``;
3. the Fig. 2b grid (128 ONUs, 128 clients, fcfs/bs x load 0.3/0.8 x
   seeds 0-3) in one ``backend="jit"`` spec against the numpy engine;
4. a folded 2-round timeline on 8 stacked PONs x 128 ONUs behind a CPS
   uplink of 0.9 x 8 x 10 Gb/s, fcfs and bs at load 0.8, likewise;
5. two clients per ONU (fcfs, load 0.8) likewise, and its fcfs phase
   program lowered again to check the Pallas waterfill is compiled in.

``--four-chips`` runs only the federated pod-axis path on four pods,
one chip each: ``repro.launch.train.train`` with four pods (two local
steps and an int8 round), then the trainer's programs
(``repro.launch.train.FedPods``) for a few local steps and one fp32 and
one int8 FedAvg round, checked against the weighted mean of the
pre-round params.  Every state leaf must be split over the four chips.

Every phase runs in this one process.  The script exits non-zero, with
no result line, unless JAX's first device is a TPU or if any check
fails.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

RTOL = 1e-6                          # device vs numpy engine; fp32 round
ATOL = 1e-6                          # fp32 round (tests/test_fed_round.py)
INT8_ATOL = 1e-3                     # int8 round vs fp32 round (same file)
OP_POINT_SYNC = 5.058100000000024    # Fig. 2b 0.8-load pin (numpy engine)
M_BITS = 26.416e6                    # the paper's CNN update, bits
FED_PODS = 4
FED_LAYERS = 8                       # depth cut of the --four-chips model
BYTES_PER_PARAM = 16                 # f32 params + AdamW moments + grads


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class Compiles:
    """Backend compilations JAX reports (count and seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def run_phase(name, fn, compiles):
    n0, s0 = compiles.n, compiles.seconds
    t0 = time.perf_counter()
    detail = fn()
    wall = time.perf_counter() - t0
    print(f"phase {name}: wall {wall:.3f} s, compile "
          f"{compiles.seconds - s0:.3f} s ({compiles.n - n0} programs); "
          f"{detail}", flush=True)


# ---------------------------------------------------------------------------
# one chip: the co-simulator
# ---------------------------------------------------------------------------


def _workload(n_clients, t_ud, m_bits=M_BITS):
    from repro.core.slicing import ClientProfile
    from repro.net import FLRoundWorkload

    return FLRoundWorkload(
        clients=[ClientProfile(client_id=i, t_ud=float(t_ud[i]), t_dl=0.0,
                               m_ud_bits=m_bits)
                 for i in range(n_clients)],
        model_bits=m_bits)


def _close(a, b) -> bool:
    import numpy as np

    return bool(np.isclose(b, a, rtol=RTOL, equal_nan=True))


def _worst(pairs) -> float:
    """Largest relative gap over (numpy, jit) value pairs."""
    worst = 0.0
    for a, b in pairs:
        if a != b:
            worst = max(worst, abs(b - a) / max(abs(a), 1e-300))
    return worst


def _round_pairs(ra, rb):
    check(set(ra.ul_done) == set(rb.ul_done), "ul_done keys differ")
    pairs = [(ra.sync_time, rb.sync_time)]
    pairs += [(ra.ul_done[k], rb.ul_done[k]) for k in ra.ul_done]
    for a, b in pairs:
        check(_close(a, b), f"jit {b} vs numpy {a} beyond rtol {RTOL}")
    return pairs


def phase_op_point():
    import numpy as np

    from repro.net import PONConfig, SweepCase, SweepSpec, simulate

    t_uds = np.random.default_rng(42).uniform(1.0, 5.0, 128)
    case = SweepCase(workload=_workload(12, t_uds), load=0.8,
                     policy="fcfs", seed=1)
    spec = SweepSpec(cases=(case,), pon=PONConfig(n_onus=128))
    host = simulate(spec)[0].sync_time
    check(host == OP_POINT_SYNC,
          f"numpy op point {host!r} != {OP_POINT_SYNC!r}")
    dev = simulate(SweepSpec(cases=(case,), pon=PONConfig(n_onus=128),
                             backend="jit"))[0].sync_time
    check(_close(host, dev), f"jit op point {dev!r} vs {host!r}")
    return f"numpy sync {host!r}, jit sync {dev!r}"


def phase_grid():
    import numpy as np

    from repro.net import PONConfig, SweepCase, SweepSpec, simulate

    wl = _workload(128, np.random.default_rng(0).uniform(1.0, 5.0, 128))
    cases = tuple(SweepCase(workload=wl, load=load, policy=policy, seed=s)
                  for policy in ("fcfs", "bs") for load in (0.3, 0.8)
                  for s in range(4))
    pon = PONConfig(n_onus=128)
    host = simulate(SweepSpec(cases=cases, pon=pon))
    t0 = time.perf_counter()
    dev = simulate(SweepSpec(cases=cases, pon=pon, backend="jit"))
    t_dev = time.perf_counter() - t0
    pairs = [p for ra, rb in zip(host, dev) for p in _round_pairs(ra, rb)]
    return (f"{len(cases)} cases, {len(pairs)} values within rtol "
            f"{RTOL} (worst {_worst(pairs):.3e}); jit call {t_dev:.3f} s")


def phase_stacked():
    import numpy as np

    from repro.net import (MultiPonTopology, PONConfig, SweepCase,
                           SweepSpec, TimelineSchedule, simulate)

    n_pons, n_onus = 8, 128
    topo = MultiPonTopology(n_pons=n_pons,
                            cps_rate_bps=0.9 * n_pons * 10e9)
    n = n_pons * n_onus
    wl = _workload(n, np.random.default_rng(0).uniform(1.0, 5.0, n))
    cases = tuple(SweepCase(workload=wl, load=0.8, policy=policy, seed=0,
                            topology=topo) for policy in ("fcfs", "bs"))
    spec = SweepSpec(cases=cases, pon=PONConfig(n_onus=n_onus),
                     schedule=TimelineSchedule(n_rounds=2), mode="folded")
    host = simulate(spec)
    t0 = time.perf_counter()
    dev = simulate(SweepSpec(cases=cases, pon=PONConfig(n_onus=n_onus),
                             schedule=TimelineSchedule(n_rounds=2),
                             mode="folded", backend="jit"))
    t_dev = time.perf_counter() - t0
    pairs = []
    for ta, tb in zip(host, dev):
        for ra, rb in zip(ta.rounds, tb.rounds):
            check(set(ra.arrived) == set(rb.arrived), "arrivals differ")
            for attr in ("sync_time", "t_start", "t_end"):
                pairs.append((getattr(ra, attr), getattr(rb, attr)))
    for a, b in pairs:
        check(_close(a, b), f"jit {b} vs numpy {a} beyond rtol {RTOL}")
    return (f"{n} ONUs x 2 policies x 2 rounds, {len(pairs)} values "
            f"within rtol {RTOL} (worst {_worst(pairs):.3e}); "
            f"jit call {t_dev:.3f} s")


def phase_sampler():
    """The device samplers against the host numpy stream, bit for bit:
    the Pallas traffic kernel (the numpy engine's sampler on a TPU) and
    the fused in-program window sampler of the device engine."""
    import jax
    import numpy as np

    from repro.kernels.ponsim.ref import sample_window_ref
    from repro.kernels.traffic.ops import (_poisson_thresholds, _tail_bound,
                                           make_stream_key,
                                           sample_arrival_bits)
    from repro.kernels.traffic.ref import WINDOW, geometric_tables
    from repro.net import PONConfig
    from repro.net.engine import PACKET_BITS
    from repro.net.traffic import burst_lambda

    pon = PONConfig(n_onus=128)
    n, inv_burst = pon.n_onus, 1.0 / pon.bg_burst_packets
    keys = np.stack([make_stream_key(s, 1, 0, 0) for s in range(4)])
    lams = np.full(len(keys), burst_lambda(
        0.8 * pon.line_rate_bps / n, pon.cycle_time_s, PACKET_BITS,
        pon.bg_burst_packets), np.float32)
    n_win = 16
    diffs = {}
    for cycle0, n_cyc in ((0, n_win * WINDOW), (1000, 300)):
        host = sample_arrival_bits(keys, cycle0, n_cyc, n, lams, inv_burst,
                                   PACKET_BITS, backend="numpy")
        tpu = sample_arrival_bits(keys, cycle0, n_cyc, n, lams, inv_burst,
                                  PACKET_BITS, backend="pallas")
        diffs[f"pallas@{cycle0}"] = int(np.sum(tpu != host))
        if cycle0 == 0:
            n_draws = _tail_bound(float(lams.max()) * WINDOW)
            thr = _poisson_thresholds(np.asarray(lams, np.float64) * WINDOW,
                                      n_draws)
            fused = jax.jit(lambda w: sample_window_ref(
                keys, thr, w, n_onus=n, n_draws=n_draws,
                burst_thr=geometric_tables(inv_burst)[1],
                packet_bits=np.float32(PACKET_BITS)))
            with jax.enable_x64(True):     # as in the device engine
                dev = np.concatenate(
                    [np.asarray(fused(np.int64(w)), np.float64)
                     for w in range(n_win)], axis=1)
            diffs["fused"] = int(np.sum(dev != host))
            total = host.size
    check(not any(diffs.values()), f"sampler cells differing from the "
          f"numpy stream: {diffs}")
    return (f"{len(keys)} streams x 128 ONUs at load 0.8, {total} cells of "
            f"{n_win} windows + a 300-cycle offset chunk; cells differing "
            f"from numpy {diffs}")


def phase_waterfill_compiled():
    """Two clients per ONU (per-ONU FL queues, fcfs at load 0.8) on the
    device engine against numpy; then lower its fcfs phase exactly as
    the engine handed it to the device and check the Pallas waterfill is
    compiled into it (a Mosaic custom call, not an interpreted loop)."""
    import jax
    import numpy as np

    from repro.kernels.ponsim import ops
    from repro.net import PONConfig, SweepCase, SweepSpec, simulate

    calls = []
    run = ops.run_phase_device

    def record(*args, **kw):
        calls.append((args, kw))
        return run(*args, **kw)

    wl = _workload(256, np.random.default_rng(0).uniform(1.0, 5.0, 256))
    case = SweepCase(workload=wl, load=0.8, policy="fcfs", seed=0)
    pon = PONConfig(n_onus=128)
    host = simulate(SweepSpec(cases=(case,), pon=pon))[0]
    ops.run_phase_device = record
    try:
        dev = simulate(SweepSpec(cases=(case,), pon=pon, backend="jit"))[0]
    finally:
        ops.run_phase_device = run
    pairs = _round_pairs(host, dev)
    args, kw = [c for c in calls if c[0][4] == "fcfs"][-1]
    prog, dyn = ops.phase_program(*args, **kw)
    with jax.enable_x64(True):
        text = prog.lower(dyn).compile().as_text()
    check("tpu_custom_call" in text,
          "the fcfs phase program holds no compiled Pallas kernel")
    return (f"256 clients on 128 ONUs, {len(pairs)} values within rtol "
            f"{RTOL} (worst {_worst(pairs):.3e}); its fcfs phase program "
            f"holds tpu_custom_call")


def one_chip(compiles):
    from repro.kernels.ponsim import ops

    run_phase("sampler", phase_sampler, compiles)
    run_phase("op_point", phase_op_point, compiles)
    run_phase("fig2b_grid", phase_grid, compiles)
    run_phase("stacked_timeline", phase_stacked, compiles)
    run_phase("waterfill_compiled", phase_waterfill_compiled, compiles)
    fallbacks = ops.host_fallbacks()
    print(f"device engine: {ops.compile_count()} phase programs traced; "
          f"host fallbacks {fallbacks}", flush=True)
    check(not any(fallbacks.values()), f"host fallbacks {fallbacks}")


# ---------------------------------------------------------------------------
# four chips: federated pod-axis rounds
# ---------------------------------------------------------------------------


def pick_fed_config(hbm_bytes: float):
    """Largest registered architecture whose replica and AdamW state
    (``BYTES_PER_PARAM`` per parameter) fit one chip's memory."""
    from repro.configs import get_config, list_architectures
    from repro.configs.base import param_count

    sized = [(param_count(get_config(a))["total"], a)
             for a in list_architectures()]
    fits = [s for s in sized if s[0] * BYTES_PER_PARAM <= hbm_bytes]
    check(bool(fits), f"no architecture fits {hbm_bytes:.3e} B")
    return max(fits)


def check_pod_split(tree, n_pods: int) -> None:
    """Every leaf's pod axis is split over ``n_pods`` devices, one pod
    slice per device: nothing sits whole on device 0."""
    import jax

    for leaf in jax.tree.leaves(tree):
        devs = {s.device for s in leaf.addressable_shards}
        check(len(devs) == n_pods
              and leaf.sharding.shard_shape(leaf.shape)[0] == 1,
              f"pod axis of a {leaf.shape} leaf not split over {n_pods} "
              f"devices: {leaf.sharding}")


def fed_rounds(cfg, n_pods: int, steps: int, per_pod_batch: int,
               seq_len: int):
    """Local steps then an fp32 and an int8 round, through the trainer's
    pod-sharded programs (``repro.launch.train.FedPods``), against the
    weighted mean of the pre-round params; raises on any failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import TokenBatcher, lm_tokens
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import FedPods
    from repro.optim.optimizers import OptimizerConfig

    mesh = make_host_mesh(model_parallel=1, pods=n_pods)
    check(mesh.devices.size == n_pods == len(set(mesh.devices.flat)),
          f"mesh {dict(mesh.shape)} does not span {n_pods} devices")
    fed = FedPods(cfg, OptimizerConfig(name="adamw", lr=1e-3), mesh, n_pods)
    state = fed.init_state(jax.random.PRNGKey(0))
    check_pod_split(state, n_pods)

    tokens = lm_tokens(400_000, cfg.vocab_size, seed=0)
    iters = [iter(TokenBatcher(tokens, per_pod_batch, seq_len, seed=i,
                               pod_index=i, n_pods=n_pods))
             for i in range(n_pods)]
    losses = []
    for _ in range(steps):
        state, metrics = fed.step(state, fed.batch([next(g) for g in iters]))
        losses.append(np.asarray(metrics["loss"]))
    check(all(np.isfinite(x).all() for x in losses), f"losses {losses}")
    check_pod_split(state, n_pods)

    weights = jnp.arange(1.0, n_pods + 1.0, dtype=jnp.float32)
    fp = fed.round_step()(state, weights)
    q8 = fed.round_step("int8")(state, weights)
    check_pod_split(fp, n_pods)
    check_pod_split(q8, n_pods)

    @jax.jit
    def gaps(pre, fp_p, q8_p):
        wn = weights / weights.sum()

        def leaf_gaps(x, f, q):
            x = x.astype(jnp.float32)
            expect = sum(wn[p] * x[p] for p in range(n_pods))
            f32 = f.astype(jnp.float32)
            excess = jnp.abs(f32[0] - expect) - (ATOL + RTOL * jnp.abs(
                expect))
            return jnp.stack([
                jnp.max(excess),                               # fp32 oracle
                jnp.max(jnp.abs(q.astype(jnp.float32) - f32)),  # int8 gap
                jnp.max(jnp.abs(f32 - f32[0:1])),              # fp32 sync
                jnp.max(jnp.abs(q - q[0:1]).astype(jnp.float32)),
                jnp.max(jnp.abs(x - x[0:1])),                  # divergence
            ])

        per = jax.tree.leaves(jax.tree.map(leaf_gaps, pre, fp_p, q8_p))
        return jnp.max(jnp.stack(per), axis=0)

    excess, int8_gap, fp_sync, q8_sync, spread = (
        float(v) for v in gaps(state.params, fp.params, q8.params))
    check(spread > 0.0, "pods did not diverge in their local steps")
    check(excess <= 0.0, f"fp32 round off the weighted mean by "
          f"{excess:.3e} beyond rtol {RTOL} + atol {ATOL}")
    check(fp_sync == 0.0 and q8_sync == 0.0,
          f"pods disagree after the round ({fp_sync}, {q8_sync})")
    check(int8_gap <= INT8_ATOL,
          f"int8 round {int8_gap:.3e} from fp32 > {INT8_ATOL}")
    return (f"{steps} local steps (loss {losses[0].mean():.4f} -> "
            f"{losses[-1].mean():.4f}), pod spread {spread:.3e}; fp32 "
            f"round within rtol {RTOL} of the weighted mean, int8 round "
            f"{int8_gap:.3e} from fp32 (atol {INT8_ATOL}), all pods "
            f"synced; every state leaf split over {n_pods} devices")


def fed_train_entry(arch: str, n_pods: int, per_pod_batch: int,
                    seq_len: int):
    """``repro.launch.train.train`` with ``n_pods`` pods (the
    ``--pods N`` CLI): two local steps and one int8 round, with its
    co-simulated round timing; checks what it returns."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.train import train

    state, history = train(
        arch=arch, smoke=False, steps_per_round=2, rounds=1,
        n_pods=n_pods, global_batch=n_pods * per_pod_batch,
        seq_len=seq_len, compress="int8", log_every=1,
        config_overrides={"n_layers": FED_LAYERS})
    check_pod_split(state, n_pods)
    check(len(history) == 1 and np.isfinite(history[0]["loss"])
          and history[0]["sync_s"] > 0.0, f"history {history}")
    spread = max(float(jnp.max(jnp.abs(x - x[0:1])))
                 for x in jax.tree.leaves(state.params))
    check(spread == 0.0, f"pods disagree after the round ({spread})")
    return (f"train(n_pods={n_pods}): round loss "
            f"{history[0]['loss']:.4f}, simulated sync "
            f"{history[0]['sync_s']!r} s; all pods synced, every state "
            f"leaf split over {n_pods} devices")


def four_chips(compiles):
    import jax

    devs = jax.devices()
    check(len(devs) == FED_PODS, f"--four-chips needs {FED_PODS} chips, "
          f"have {len(devs)}")
    hbm = devs[0].memory_stats()["bytes_limit"]
    n_params, arch = pick_fed_config(hbm)
    from repro.configs import get_config

    cfg = get_config(arch).replace(grad_accum=1, n_layers=FED_LAYERS)
    print(f"fed model: {arch} ({n_params:,} params x {BYTES_PER_PARAM} B "
          f"fits {hbm:,} B per chip), full width, depth cut to "
          f"{FED_LAYERS} layers; {FED_PODS} pods on {FED_PODS} chips",
          flush=True)
    run_phase("fed_train_entry",
              lambda: fed_train_entry(arch, FED_PODS, per_pod_batch=4,
                                      seq_len=512), compiles)
    run_phase("fed_rounds",
              lambda: fed_rounds(cfg, FED_PODS, steps=3, per_pod_batch=4,
                                 seq_len=512), compiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the federated path on four chips")
    args = ap.parse_args(argv)

    from benchmarks._env import use_compile_cache

    cache = use_compile_cache(ROOT)
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}; jax {jax.__version__}; compile cache "
          f"{cache}", flush=True)
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (first device is {dev.platform})",
              file=sys.stderr)
        return 1
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(compiles)
    except CheckFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total: wall {time.perf_counter() - t0:.3f} s, compile "
          f"{compiles.seconds:.3f} s over {compiles.n} programs", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
