"""compiles_in_window: backend compilations JAX reported inside the
measured window (jax.monitoring); every program should have been built
and cached in set-up, so this reads 0."""


def read(run):
    return run.compiles
