"""Distribution tests on a small host mesh (subprocess isolation for the
device-count env var, since the main test process must keep 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import param_spec


class TestShardingRules:
    MESH = None

    @classmethod
    def setup_class(cls):
        # an abstract mesh over 1 device would make every axis size 1;
        # use jax's AbstractMesh for pure spec logic
        from jax.sharding import AbstractMesh

        cls.MESH = AbstractMesh((16, 16), ("data", "model"))

    def spec(self, names, shape, cfg, **kw):
        return param_spec(names, shape, cfg, self.MESH, **kw)

    def test_column_row_rules(self):
        cfg = get_config("llama3-8b")
        assert self.spec(["units", "b0", "mixer", "wq"],
                         (32, 4096, 4096), cfg) == P(None, None, "model")
        assert self.spec(["units", "b0", "mixer", "wo"],
                         (32, 4096, 4096), cfg) == P(None, "model", None)
        assert self.spec(["units", "b0", "mlp", "w_down"],
                         (32, 14336, 4096), cfg) == P(None, "model", None)

    def test_embedding_vocab_fallback(self):
        # mamba2 vocab 50280 is NOT divisible by 16 -> shard d_model instead
        cfg = get_config("mamba2-780m")
        assert self.spec(["embed"], (50280, 1536), cfg) == P(None, "model")
        cfg2 = get_config("llama3-8b")
        assert self.spec(["embed"], (128256, 4096), cfg2) == P("model", None)

    def test_moe_expert_parallel_vs_fallback(self):
        arctic = get_config("arctic-480b")     # 128 experts: EP over model
        s = self.spec(["units", "b0", "moe", "w_gate"],
                      (1, 128, 7168, 4864), arctic)
        assert s[1] == "model"
        mixtral = get_config("mixtral-8x22b")  # 8 experts < 16: tensor shard
        s2 = self.spec(["units", "b0", "moe", "w_gate"],
                       (1, 8, 6144, 16384), mixtral)
        assert s2[1] is None and s2[3] == "model"

    def test_fsdp_adds_data_axis(self):
        cfg = get_config("arctic-480b")        # fsdp=True
        s = self.spec(["units", "b0", "mixer", "wq"],
                      (1, 7168, 7168), cfg)
        assert s == P(None, "data", "model")

    def test_norms_replicated(self):
        cfg = get_config("llama3-8b")
        assert self.spec(["units", "b0", "mix_norm", "scale"],
                         (32, 4096), cfg) == P(None)


@pytest.mark.slow
class TestSmallMeshEndToEnd:
    """Run a tiny federated train + round step on 8 fake devices."""

    def test_fed_steps_run(self, tmp_path):
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from repro.configs import get_config
            from repro.dist import stepfns
            from repro.optim.optimizers import OptimizerConfig
            from repro.launch.mesh import make_host_mesh, batch_axes
            from jax.sharding import NamedSharding, PartitionSpec as P

            cfg = get_config("olmo-1b", smoke=True).replace(grad_accum=2)
            opt = OptimizerConfig(name="adamw", lr=1e-2)
            mesh = make_host_mesh(model_parallel=2, pods=2)  # (2,2,2)
            n_pods = 2
            with mesh:
                state = stepfns.init_fed_state(
                    jax.random.PRNGKey(0), cfg, opt, n_pods)
                step = stepfns.make_fed_train_step(cfg, opt)
                B, S = 8, 16
                tokens = jax.random.randint(
                    jax.random.PRNGKey(1), (n_pods, B // n_pods, S),
                    0, cfg.vocab_size)
                batch = {"tokens": tokens, "labels": tokens}
                state2, metrics = jax.jit(step)(state, batch)
                loss = float(metrics["loss"].mean())
                assert loss > 0 and loss == loss, loss

                # pods diverge after local steps
                p0 = jax.tree.leaves(state2.params)[0]
                assert abs(float(p0[0].mean() - p0[1].mean())) >= 0

                round_step = stepfns.make_fed_round_step(cfg, compress="int8")
                weights = jnp.array([1.0, 3.0])
                state3 = jax.jit(round_step)(state2, weights)
                # after the round, every pod holds the same params
                for leaf in jax.tree.leaves(state3.params):
                    a = jnp.asarray(leaf)
                    assert bool(jnp.allclose(
                        a[0].astype(jnp.float32),
                        a[1].astype(jnp.float32), atol=1e-5)), leaf.shape
            print("FED_OK", loss)
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src")
        )
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert "FED_OK" in out.stdout, out.stdout + out.stderr


def test_train_splits_pods_over_devices():
    """``launch.train`` with 2 pods on 2 devices keeps every leaf of the
    pod-stacked state split over the pod axis, one pod per device,
    through its local steps and the round."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        from repro.launch.train import train

        state, history = train(arch="olmo-1b", smoke=True,
                               steps_per_round=2, rounds=1, n_pods=2,
                               global_batch=4, seq_len=16)
        for leaf in jax.tree.leaves(state):
            devs = {s.device for s in leaf.addressable_shards}
            assert len(devs) == 2, (leaf.shape, leaf.sharding)
            assert leaf.sharding.shard_shape(leaf.shape)[0] == 1, (
                leaf.shape, leaf.sharding)
        print("PODS_SPLIT_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert "PODS_SPLIT_OK" in out.stdout, out.stdout + out.stderr[-4000:]


@pytest.mark.slow
class TestDryRunSmall:
    """One real dry-run cell in a subprocess (512 fake devices)."""

    def test_olmo_train_cell(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src")
        )
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "olmo-1b", "--shape", "train_4k", "--mesh", "single"],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert "1/1 cells OK" in out.stdout, out.stdout + out.stderr
