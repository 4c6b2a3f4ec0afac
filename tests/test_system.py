"""End-to-end behaviour tests: the paper's system claims, in miniature.

A reduced FL task (16 clients, LEAF-style synthetic FEMNIST, real JAX
training) co-simulated with the PON: accuracy must improve over rounds,
more clients must reach higher accuracy (Fig 2a), and BS must beat FCFS on
wall-clock time-to-accuracy at high load (the 36%-saving claim, reduced).
"""
import jax
import numpy as np
import pytest

from repro.data import build_federated_cnn_clients
from repro.fl import (
    CompressorConfig,
    CoSimConfig,
    CPSServer,
    FLNetworkCoSim,
    SelectionConfig,
)
from repro.fl.client import LocalTrainConfig
from repro.models import cnn
from repro.net.sim import PONConfig


def _build(n_clients=8, fraction=1.0, policy="bs", load=0.8, seed=0,
           failure_prob=0.0, scheme="none", n_classes=62):
    clients, test = build_federated_cnn_clients(
        n_clients=n_clients,
        samples_per_client=48,
        loss_fn=cnn.loss_fn,
        train_cfg=LocalTrainConfig(lr=0.05, batch_size=16, local_epochs=1),
        seed=seed,
    )
    params = cnn.init_params(jax.random.PRNGKey(seed))
    server = CPSServer(
        global_params=params,
        clients=clients,
        selection=SelectionConfig(strategy="fraction", fraction=fraction),
        compression=CompressorConfig(scheme=scheme),
        failure_prob=failure_prob,
        seed=seed,
    )
    cfg = CoSimConfig(
        policy=policy,
        total_load=load,
        pon=PONConfig(n_onus=max(n_clients, 8)),
        timing_seeds=1,
    )
    test_batch = {"images": test["images"][:256], "labels": test["labels"][:256]}
    def eval_fn(p):
        return cnn.accuracy(p, test_batch)
    return FLNetworkCoSim(server, cfg), eval_fn


@pytest.mark.slow
class TestEndToEnd:
    def test_accuracy_improves_over_rounds(self):
        sim, eval_fn = _build(n_clients=8)
        res = sim.run(n_rounds=6, eval_fn=eval_fn)
        accs = [r["eval_metric"] for r in res.rounds]
        assert accs[-1] > accs[0] + 0.05
        assert accs[-1] > 0.10          # far above 1/62 chance

    def test_more_clients_higher_accuracy(self):
        """Fig 2a: involvement fraction drives saturated accuracy."""
        sim_small, ev = _build(n_clients=8, fraction=0.25, seed=1)
        sim_full, ev2 = _build(n_clients=8, fraction=1.0, seed=1)
        acc_small = sim_small.run(n_rounds=5, eval_fn=ev).rounds[-1][
            "eval_metric"]
        acc_full = sim_full.run(n_rounds=5, eval_fn=ev2).rounds[-1][
            "eval_metric"]
        assert acc_full >= acc_small - 0.02

    def test_bs_faster_than_fcfs_to_same_accuracy(self):
        """The headline claim: identical learning curve, less wall-clock."""
        sim_bs, ev = _build(policy="bs", load=0.8, seed=2)
        sim_fcfs, ev2 = _build(policy="fcfs", load=0.8, seed=2)
        res_bs = sim_bs.run(n_rounds=3, eval_fn=ev)
        res_fcfs = sim_fcfs.run(n_rounds=3, eval_fn=ev2)
        # same seeds -> identical training; BS strictly faster per round
        assert res_bs.sync_time_s < res_fcfs.sync_time_s
        assert res_bs.total_time_s < res_fcfs.total_time_s

    def test_survives_client_failures(self):
        sim, ev = _build(failure_prob=0.3, seed=3)
        res = sim.run(n_rounds=4, eval_fn=ev)
        assert len(res.rounds) == 4
        assert all(np.isfinite(r["mean_loss"]) or r["n_arrived"] == 0
                   for r in res.rounds)

    def test_compression_reduces_slice_demand(self):
        """int8 updates shrink M_i^UD and hence the BS slice bandwidth."""
        from repro.core.slicing import ClientProfile, compute_slice

        full = [ClientProfile(i, 1.0 + i, 0.01, 26.416e6) for i in range(4)]
        comp = [ClientProfile(i, 1.0 + i, 0.01, 26.416e6 / 4) for i in range(4)]
        # the M_i^UD lever acts on the paper's demand formula (line 8)
        s_full = compute_slice(full, 0.0, 10.0, 10e9, sizing="paper")
        s_comp = compute_slice(comp, 0.0, 10.0, 10e9, sizing="paper")
        assert s_comp.bandwidth_bps < s_full.bandwidth_bps / 3.5
        # and the corrected sizing still demands no more for smaller updates
        d_full = compute_slice(full, 0.0, 10.0, 10e9)
        d_comp = compute_slice(comp, 0.0, 10.0, 10e9)
        assert d_comp.bandwidth_bps <= d_full.bandwidth_bps + 1e-6


class TestCompileCacheDir:
    """Entry points keep JAX's compile cache where the environment says,
    else at a fixed path inside the checkout."""

    def test_environment_dir_is_used_as_is(self, monkeypatch, tmp_path):
        from benchmarks._env import use_compile_cache

        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache(str(tmp_path)) == env_dir
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_dir_inside_the_checkout(self, monkeypatch, tmp_path):
        from benchmarks._env import use_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = use_compile_cache(str(tmp_path))
            assert path == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
