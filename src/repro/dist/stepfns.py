"""Sharded step functions: train, federated train/round, prefill, decode.

All step functions are pure and jit-able; sharding comes from the caller
(``jax.jit`` in/out shardings built by ``repro.launch.specs`` from the
rules in ``repro.dist.sharding``), so the same code runs on one host
device, the 8-device test mesh and the 512-chip production mesh.

Federated layout: every leaf of a federated ``TrainState`` carries a
leading ``n_pods`` axis, sharded over the ``pod`` mesh axis (one pod per
EC-node site, DESIGN.md §3). ``make_fed_train_step`` vmaps the single-pod
step over that axis — local SGD with no cross-pod traffic — and
``make_fed_round_step`` performs the weighted FedAvg reduction whose
upload payload (``M_i^UD``) the paper's BS slice is provisioned for.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import fedops
from repro.fl.compression import CompressorConfig, compressed_update_bits
from repro.models import lm
from repro.optim.optimizers import (
    OptimizerConfig,
    OptState,
    apply_updates,
    init_opt_state,
)


class TrainState(NamedTuple):
    params: Any
    opt: OptState


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def init_train_state(key, cfg: ModelConfig,
                     opt_cfg: OptimizerConfig) -> TrainState:
    params = lm.init_params(key, cfg)
    return TrainState(params=params, opt=init_opt_state(params, opt_cfg))


def init_fed_state(key, cfg: ModelConfig, opt_cfg: OptimizerConfig,
                   n_pods: int) -> TrainState:
    """Replicate one init across a leading ``n_pods`` axis on every leaf.

    All pods start each experiment from the same global model (the CPS
    broadcast); they diverge through local steps and re-sync at rounds.
    """
    base = init_train_state(key, cfg, opt_cfg)
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n_pods,) + l.shape), base
    )


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    schedule: Optional[Callable] = None,
    grad_shardings: Optional[Any] = None,
) -> Callable:
    """Single-pod SGD step with microbatch grad accumulation.

    ``step(state, batch) -> (state, metrics)`` where batch leaves are
    ``(B, ...)``; with ``cfg.grad_accum > 1`` the batch is split into
    ``grad_accum`` microbatches scanned sequentially (grads averaged in
    fp32), so the global batch fits regardless of per-device memory.

    ``grad_shardings`` (a pytree of ``NamedSharding`` matching the param
    tree) pins the accumulated fp32 gradients — and the averaged grads
    fed to the optimizer — to the parameters' layout via
    ``jax.lax.with_sharding_constraint``. Without it, GSPMD is free to
    keep the scan carry in a different layout than the ZeRO-sharded
    optimizer update consumes, which shows up as involuntary resharding
    (reported by XLA between the grad-accum scan and the update).
    """
    accum = max(int(cfg.grad_accum), 1)

    def constrain(grads):
        if grad_shardings is None:
            return grads
        return jax.lax.with_sharding_constraint(grads, grad_shardings)

    def loss_of(params, batch):
        return lm.loss_fn(params, cfg, batch)

    def step(state: TrainState, batch):
        if cfg.dtype != "float32":
            return _step(state, batch)
        # a TPU's default precision rounds float32 matmul operands to
        # bfloat16, so a float32 model asks for float32 products
        with jax.default_matmul_precision("highest"):
            return _step(state, batch)

    def _step(state: TrainState, batch):
        if accum > 1:
            micro = jax.tree.map(
                lambda x: x.reshape(
                    (accum, x.shape[0] // accum) + x.shape[1:]
                ),
                batch,
            )

            def body(carry, mb):
                g_sum, l_sum = carry
                loss, g = jax.value_and_grad(loss_of)(state.params, mb)
                g_sum = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), g_sum, g
                )
                return (constrain(g_sum), l_sum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (g_sum, l_sum), _ = jax.lax.scan(
                body, (constrain(zeros), jnp.zeros((), jnp.float32)), micro
            )
            g_sum = constrain(g_sum)
            grads = jax.tree.map(
                lambda g, p: (g / accum).astype(p.dtype),
                g_sum, state.params,
            )
            loss = l_sum / accum
        else:
            loss, grads = jax.value_and_grad(loss_of)(state.params, batch)
        grads = constrain(grads)

        lr = jnp.asarray(
            schedule(state.opt.step) if schedule is not None else opt_cfg.lr,
            jnp.float32,
        )
        with jax.named_scope("optimizer"):
            params, opt, gnorm = apply_updates(
                state.params, grads, state.opt, opt_cfg, lr=lr,
                decay_mask=lm.weight_decay_mask(state.params),
            )
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return TrainState(params=params, opt=opt), metrics

    return step


def make_fed_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    schedule: Optional[Callable] = None,
    grad_shardings: Optional[Any] = None,
    spmd_axis_name: Optional[str] = None,
) -> Callable:
    """Per-pod local step over the federated (pod-stacked) state.

    ``batch`` leaves are ``(n_pods, per_pod_B, ...)``; the single-pod
    step is vmapped over the pod axis, so under the ``("pod", "data",
    "model")`` mesh each pod trains on its own shard with zero cross-pod
    communication — exactly the paper's local-epoch phase.

    ``grad_shardings`` are *per-pod* (pod axis stripped) shardings for
    the accumulated gradients; pass ``spmd_axis_name="pod"`` so vmap
    prepends the pod mesh axis to every constraint inside the step.
    """
    base = make_train_step(cfg, opt_cfg, schedule,
                           grad_shardings=grad_shardings)

    def fed_step(state: TrainState, batch):
        return jax.vmap(base, spmd_axis_name=spmd_axis_name)(state, batch)

    return fed_step


# ---------------------------------------------------------------------------
# federated round (the M_i^UD traffic)
# ---------------------------------------------------------------------------


def make_fed_round_step(cfg: ModelConfig, compress: Optional[str] = None,
                        topk_frac: float = 0.05,
                        error_feedback: bool = False) -> Callable:
    """Weighted FedAvg across the pod axis (``repro.fl.aggregation``
    semantics, expressed as one cross-pod reduce).

    ``round_step(state, weights) -> state`` with ``weights`` shaped
    ``(n_pods,)`` (client data sizes). ``compress`` in
    ``{None, "none", "int8", "topk", "int8+topk"}`` round-trips each
    pod's update through ``repro.fl.compression`` before averaging.
    Optimizer moments stay pod-local (local adaptive state), mirroring
    the host-side CPS which only ships model weights.

    With ``error_feedback=True`` the signature becomes
    ``round_step(state, weights, residuals) -> (state, residuals)``:
    each pod carries the fp32 residual of what compression dropped and
    adds it to its next upload (``init_round_residuals`` builds the
    initial zeros) — the in-graph mirror of the host-side
    ``fl.compression`` error-feedback pipeline.

    The wire size of the upload this step implies is
    ``fed_update_bits(cfg, compress)`` — the co-sim's slice sizing
    derives from that, not from a hard-coded constant.
    """
    scheme = fedops.check_scheme(compress)

    if error_feedback:
        def round_step_ef(state: TrainState, weights, residuals):
            with jax.named_scope("fedavg"):
                params, new_res = fedops.fedavg_pods(
                    state.params, weights, scheme=scheme,
                    topk_frac=topk_frac, residuals=residuals,
                )
            return TrainState(params=params, opt=state.opt), new_res

        return round_step_ef

    def fed_round(state: TrainState, weights) -> TrainState:
        with jax.named_scope("fedavg"):
            params = fedops.fedavg_pods(
                state.params, weights, scheme=scheme, topk_frac=topk_frac
            )
        return TrainState(params=params, opt=state.opt)

    return fed_round


def init_round_residuals(state: TrainState):
    """Zero error-feedback residuals for ``make_fed_round_step(...,
    error_feedback=True)`` — pod-stacked fp32, like the params."""
    return fedops.init_residuals(state.params)


# ---------------------------------------------------------------------------
# asynchronous federated round (FedBuff on the pod axis)
# ---------------------------------------------------------------------------


class AsyncRoundState(NamedTuple):
    """Cross-round state of the async (FedBuff) federated loop.

    ``global_params``: pod-stacked broadcast copies of the current
    global model (every pod holds the same rows). ``refs``: each pod's
    *download reference* — the global model it last synced to, which
    its next upload delta is computed against (pods that downloaded at
    different rounds hold different refs). ``pending``: each pod's
    snapshotted fp32 update delta — the payload travelling on the wire
    while the pod's upload is in flight.
    """

    global_params: Any
    refs: Any
    pending: Any


def init_async_state(state: TrainState) -> AsyncRoundState:
    """Fresh async state: every pod synced to the same global model,
    nothing in flight."""
    return AsyncRoundState(
        global_params=state.params,
        refs=state.params,
        pending=jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), state.params
        ),
    )


def make_async_round_step(cfg: ModelConfig, compress: Optional[str] = None,
                          topk_frac: float = 0.05,
                          error_feedback: bool = False,
                          server_lr: float = 1.0,
                          staleness_power: float = 0.5,
                          quorum_frac: Optional[float] = None,
                          quorum_expected: Optional[int] = None) -> Callable:
    """Buffered asynchronous aggregation (FedBuff) across the pod axis.

    ``async_step(state, astate, weights, arrived, staleness, frac,
    snap, rejoin) -> (state, astate)`` where all mask/weight args are
    ``(n_pods,)`` arrays driven by the network timeline
    (``repro.net.timeline`` async mode — arrivals, staleness and
    partial fractions per aggregation event):

    * ``snap`` (bool): pods that just finished their local round —
      their update delta ``params - refs`` is snapshotted into
      ``pending`` (the upload begins; later training never leaks into
      the in-flight payload);
    * ``arrived`` (bool): pods whose upload reached the CPS this round
      — their pending deltas merge into the global, weighted
      ``w_i · frac_i / (1+τ_i)^p`` (``staleness`` τ in rounds,
      ``frac`` the served fraction for partial updates);
    * ``rejoin`` (bool): pods that resync to the new global
      (arrived pods re-entering, and drop-policy pods whose update was
      discarded) — their params and refs take the fresh broadcast;
      stragglers still uploading keep theirs.

    Optimizer moments stay pod-local, as in the sync round step. With
    ``error_feedback=True`` the signature grows a trailing
    ``residuals`` arg and returns ``(state, astate, residuals)`` —
    arrived pods' wire encodings run through the same error-feedback
    pipeline as the sync compressed round.

    ``quorum_frac`` threads the in-graph quorum gate through to
    ``fedops.fedbuff_pods``: with fewer than ``ceil(quorum_frac *
    quorum_expected)`` arrivals (default ``n_pods``) the merge degrades
    to the previous global model. Rejoining pods then resync to that
    *unchanged* global — the degraded-round semantics of
    ``repro.net.timeline``'s ``quorum_met=False`` rounds.
    """
    scheme = fedops.check_scheme(compress)

    def _advance(state, astate, weights, arrived, staleness, frac,
                 snap, rejoin, residuals):
        pending = jax.tree.map(
            lambda p, ref, pen: jnp.where(
                fedops._bmask(snap, pen),
                (p.astype(jnp.float32) - ref.astype(jnp.float32)), pen,
            ),
            state.params, astate.refs, astate.pending,
        )
        merged = fedops.fedbuff_pods(
            pending, astate.global_params, weights, arrived, staleness,
            server_lr=server_lr, scheme=scheme, topk_frac=topk_frac,
            staleness_power=staleness_power, frac=frac,
            residuals=residuals,
            quorum_frac=quorum_frac, n_expected=quorum_expected,
        )
        new_global, new_res = merged if error_feedback else (merged, None)
        take = lambda new, old: jax.tree.map(  # noqa: E731
            lambda n, o: jnp.where(fedops._bmask(rejoin, o), n, o),
            new, old,
        )
        params = take(new_global, state.params)
        refs = take(new_global, astate.refs)
        new_astate = AsyncRoundState(
            global_params=new_global, refs=refs, pending=pending
        )
        return TrainState(params=params, opt=state.opt), new_astate, new_res

    if error_feedback:
        return _advance

    def async_step(state, astate, weights, arrived, staleness, frac,
                   snap, rejoin):
        state, astate, _ = _advance(
            state, astate, weights, arrived, staleness, frac, snap,
            rejoin, None,
        )
        return state, astate

    return async_step


def fed_update_bits(cfg: ModelConfig, compress: Optional[str] = "int8",
                    topk_frac: float = 0.05) -> int:
    """Wire bits of ONE pod's upload under ``compress`` (``M_i^UD``).

    Derived from the real parameter tree via ``eval_shape`` (no
    allocation) and ``repro.fl.compression``'s accounting, so the co-sim
    slice demand tracks the actual sharded update payload.
    """
    scheme = fedops.check_scheme(compress)
    params = jax.eval_shape(
        lambda k: lm.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    comp = CompressorConfig(scheme=scheme, topk_frac=topk_frac)
    return compressed_update_bits(params, comp)


def payload_summary(cfg: ModelConfig,
                    schemes=("none", "int8"),
                    topk_frac: float = 0.05) -> dict:
    """Wire-size provenance of one pod's upload per compression scheme.

    The observability layer stamps this into metrics reports and JSONL
    round logs so a timing artifact carries the payload sizes it was
    produced under (``model_bits`` is the fp32 downlink broadcast).
    """
    bits = {str(s): int(fed_update_bits(cfg, s, topk_frac))
            for s in schemes}
    return {
        "model_bits": bits.get("none", int(fed_update_bits(cfg, "none"))),
        "upload_bits": bits,
        "topk_frac": topk_frac,
    }


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """``step(params, tokens, cache, extra_embeds=None) -> (logits, cache)``."""

    def step(params, tokens, cache, extra_embeds=None):
        return lm.prefill(params, cfg, tokens, cache, extra_embeds)

    return step


def make_decode_step(cfg: ModelConfig) -> Callable:
    """``step(params, token, cache) -> (logits, cache)`` — one token."""

    def step(params, token, cache):
        return lm.decode_step(params, cfg, token, cache)

    return step
