"""Optimizers as pure pytree transforms (shard-compatible by construction).

The optimizer state mirrors the parameter tree leaf-for-leaf, so whatever
sharding the parameters carry applies to the state (plus the ZeRO option in
``repro.dist.sharding`` that additionally shards moments over the data axis).
``opt_state_dtype`` controls moment precision (bf16 moments halve the HBM
footprint of Adam — required to fit arctic-480b, see DESIGN.md).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"            # "sgd" | "momentum" | "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: str = "float32"


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: object          # first moment (or momentum buffer); None-like for sgd
    nu: object          # second moment; None-like for sgd/momentum


def init_opt_state(params, cfg: OptimizerConfig) -> OptState:
    dt = jnp.dtype(cfg.state_dtype)
    def zeros():
        return jax.tree.map(lambda l: jnp.zeros(l.shape, dt), params)
    step = jnp.zeros((), jnp.int32)
    if cfg.name == "sgd":
        empty = jax.tree.map(lambda l: jnp.zeros((0,), dt), params)
        return OptState(step, empty, empty)
    if cfg.name == "momentum":
        empty = jax.tree.map(lambda l: jnp.zeros((0,), dt), params)
        return OptState(step, zeros(), empty)
    return OptState(step, zeros(), zeros())


def _clip_by_global_norm(grads, max_norm: float):
    sq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)
    )
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm


def apply_updates(params, grads, state: OptState, cfg: OptimizerConfig,
                  lr: Optional[jnp.ndarray] = None, decay_mask=None):
    """Returns (new_params, new_state, grad_norm).

    AdamW's weight decay applies to the leaves that ``decay_mask`` (a
    pytree of bools like ``params``) marks, by default those of rank 2
    or more; a model whose layers are stacked on a leading axis passes
    its own (``repro.models.lm.weight_decay_mask``)."""
    lr = cfg.lr if lr is None else lr
    if cfg.grad_clip:
        grads, gnorm = _clip_by_global_norm(grads, cfg.grad_clip)
    else:
        gnorm = jnp.zeros(())
    step = state.step + 1
    sdt = jnp.dtype(cfg.state_dtype)

    if cfg.name == "sgd":
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32))
            .astype(p.dtype),
            params, grads,
        )
        return new_params, OptState(step, state.mu, state.nu), gnorm

    if cfg.name == "momentum":
        mu = jax.tree.map(
            lambda m, g: (0.9 * m.astype(jnp.float32) + g.astype(jnp.float32))
            .astype(sdt),
            state.mu, grads,
        )
        new_params = jax.tree.map(
            lambda p, m: (p.astype(jnp.float32) - lr * m.astype(jnp.float32))
            .astype(p.dtype),
            params, mu,
        )
        return new_params, OptState(step, mu, state.nu), gnorm

    # adamw
    stepf = step.astype(jnp.float32)
    mu = jax.tree.map(
        lambda m, g: (cfg.b1 * m.astype(jnp.float32)
                      + (1 - cfg.b1) * g.astype(jnp.float32)).astype(sdt),
        state.mu, grads,
    )
    nu = jax.tree.map(
        lambda v, g: (cfg.b2 * v.astype(jnp.float32)
                      + (1 - cfg.b2) * jnp.square(g.astype(jnp.float32)))
        .astype(sdt),
        state.nu, grads,
    )
    bc1 = 1 - cfg.b1 ** stepf
    bc2 = 1 - cfg.b2 ** stepf

    def upd(p, m, v, decay):
        mhat = m.astype(jnp.float32) / bc1
        vhat = v.astype(jnp.float32) / bc2
        u = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay and decay:           # decay matrices only
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    if decay_mask is None:
        decay_mask = jax.tree.map(lambda p: p.ndim >= 2, params)
    new_params = jax.tree.map(upd, params, mu, nu, decay_mask)
    return new_params, OptState(step, mu, nu), gnorm
