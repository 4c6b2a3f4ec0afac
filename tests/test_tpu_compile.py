"""Compile-only checks of the main path's device programs for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests lower the co-simulator's phase programs and both Pallas kernels
for a described ``v5e:2x2`` chip and compile them at real widths (128
ONUs per PON).  That catches what interpret-mode tests cannot: block
shapes off the (8, 128) tiling, 64-bit values inside a kernel, and
programs the TPU compiler does not finish (a float64 ``cumsum`` did
not compile in minutes).  Nothing runs, so nothing here checks results.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and pytest-xdist
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.slicing import ClientProfile
from repro.kernels.ponsim import ops
from repro.kernels.ponsim.kernel import waterfill_grants_pallas
from repro.kernels.traffic.kernel import _sample_tpu_jit
from repro.kernels.traffic.ops import _tail_bound
from repro.kernels.traffic.ref import WINDOW, geometric_tables
from repro.net import (FLRoundWorkload, MultiPonTopology, PONConfig,
                       SweepCase, SweepSpec, simulate)
from repro.net.engine import PACKET_BITS
from repro.net.traffic import burst_lambda

N_ONUS = 128
M_BITS = 26.416e6


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _workload(n_clients):
    t_ud = np.random.default_rng(0).uniform(1.0, 5.0, n_clients)
    return FLRoundWorkload(
        clients=[ClientProfile(client_id=i, t_ud=float(t_ud[i]), t_dl=0.0,
                               m_ud_bits=M_BITS)
                 for i in range(n_clients)],
        model_bits=M_BITS)


def _phase_call(cases, mode):
    """Arguments of the last ``mode`` phase a jit sweep hands the device
    engine, captured without running anything."""
    calls = []

    def capture(cfg, lay, rem0, ready, mode_, **kw):
        calls.append((cfg, lay, rem0, ready, mode_, kw))
        return np.full(rem0.shape, 0.5), np.zeros(rem0.shape)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "run_phase_device", capture)
        simulate(SweepSpec(cases=tuple(cases), pon=PONConfig(n_onus=N_ONUS),
                           backend="jit"))
    return [c for c in calls if c[4] == mode][-1]


PHASES = {
    # Fig. 2b: one client per ONU -> the scalar-S fast path, bg traffic
    "fcfs_fast_bg": (lambda: [SweepCase(workload=_workload(N_ONUS),
                                        load=0.8, policy="fcfs", seed=0)],
                     "fcfs"),
    # two clients per ONU -> per-ONU FL queues and the waterfill kernel
    "fcfs_many_per_onu": (lambda: [SweepCase(workload=_workload(2 * N_ONUS),
                                             load=0.8, policy="fcfs",
                                             seed=0)],
                          "fcfs"),
    # bs slots on two stacked PONs behind a contended CPS uplink
    "bs_cps": (lambda: [SweepCase(
        workload=_workload(2 * N_ONUS), load=0.8, policy="bs", seed=0,
        topology=MultiPonTopology(n_pons=2, cps_rate_bps=0.9 * 2 * 10e9))],
        "bs"),
}


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pallas", "jnp"])
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_program_compiles(one_chip, phase, use_pallas):
    make_cases, mode = PHASES[phase]
    cfg, lay, rem0, ready, mode, kw = _phase_call(make_cases(), mode)
    prog, dyn = ops.phase_program(cfg, lay, rem0, ready, mode,
                                  use_pallas=use_pallas, **kw)
    with jax.enable_x64(True):
        shapes = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                          sharding=one_chip)
                  for k, v in dyn.items()}
        text = prog.lower(shapes).compile().as_text()
    # the waterfill kernel sits in every fcfs program with bg traffic
    assert ("tpu_custom_call" in text) == (use_pallas and mode == "fcfs")


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("n", [128, 1024, 4096])
def test_waterfill_kernel_compiles(one_chip, n, x64):
    # the engine traces the kernel inside its scoped x64 context
    with jax.enable_x64(x64):
        b = jax.ShapeDtypeStruct((16, n), jnp.float32, sharding=one_chip)
        cap = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
        text = jax.jit(waterfill_grants_pallas).lower(
            b, b, cap).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [16, 2048])
def test_traffic_kernel_compiles(one_chip, rows):
    lam = burst_lambda(0.8 * 10e9 / N_ONUS, 1e-3, PACKET_BITS, 16.0)
    n_draws = _tail_bound(lam * WINDOW)
    keys = jax.ShapeDtypeStruct((rows, 2), jnp.uint32, sharding=one_chip)
    win0 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    thr = jax.ShapeDtypeStruct((rows, n_draws), jnp.int32,
                               sharding=one_chip)
    burst_thr = jax.ShapeDtypeStruct(
        geometric_tables(1.0 / 16.0)[1].shape, jnp.int32, sharding=one_chip)
    text = _sample_tpu_jit.lower(
        keys, win0, thr, burst_thr, lo=0, n_cycles=1024, n_onus=N_ONUS,
        n_draws=n_draws, packet_bits=PACKET_BITS, block_windows=4,
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
