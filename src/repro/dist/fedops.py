"""In-graph cross-pod federated collectives.

The federated state keeps a leading ``n_pods`` axis on every leaf (sharded
over the ``pod`` mesh axis). A FedAvg round is then a weighted reduction
over that axis followed by a broadcast — on a real fleet this is the
cross-site ``M_i^UD`` upload the BS slice is sized for, so the round step
optionally pushes each pod's update through the same int8/top-k
compression pipeline as ``repro.fl.compression`` before averaging.

Compression operates on the *delta from pod 0* (the pods start each round
from identical params, so inter-pod deltas are small and quantise far
more accurately than raw weights). Reconstruction is exact for pod 0
(zero delta), so the scheme degrades gracefully to plain FedAvg as the
pods converge.

Error feedback (matching the host-side ``fl.compression`` pipeline):
each pod carries an fp32 residual of what compression dropped last
round; the residual is added to the next round's delta before encoding,
so compression noise averages out instead of biasing FedAvg. The
residual pytree lives in the round state (``init_residuals`` /
``fedavg_pods(..., residuals=...)``) and stays pod-local — it is never
transmitted.

Asynchronous rounds (FedBuff): ``fedbuff_pods`` applies a *buffered*
staleness-weighted delta merge instead of a full average — only the
pods whose upload reached the CPS this round (``arrived``) contribute,
each discounted by ``1/(1+τ)^p`` for its staleness ``τ`` (rounds since
it downloaded the model it trained on) and optionally scaled by a
served *fraction* (the network layer's ``deadline_policy="partial"``).
The merge consumes snapshotted update deltas (one per pod, frozen when
the pod finished its local round — its upload payload), so a pod whose
upload is still in flight contributes exactly the bits it put on the
wire, not whatever its parameters drifted to since. Host-side mirror:
``repro.fl.aggregation.fedbuff_merge``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.fl.compression import (
    dequantize_int8,
    quantize_int8,
    topk_sparsify,
)

SCHEMES = ("none", "int8", "topk", "int8+topk")


def check_scheme(scheme) -> str:
    """Normalise/validate a compression scheme name (None -> "none")."""
    scheme = scheme or "none"
    if scheme not in SCHEMES:
        raise ValueError(
            f"unknown compression scheme {scheme!r}; have {SCHEMES}"
        )
    return scheme


def pod_weighted_mean(leaf: jnp.ndarray, w_norm: jnp.ndarray) -> jnp.ndarray:
    """Weighted mean over the leading pod axis, broadcast back to all pods.

    Same semantics as ``repro.fl.aggregation.fedavg`` (fp32 accumulate,
    cast back to the leaf dtype) but expressed over a stacked axis so it
    lowers to a single cross-pod reduce under GSPMD. The contraction
    asks for full f32 precision: a TPU's default f32 matmul precision
    rounds the operands to bf16.
    """
    g = jnp.tensordot(w_norm, leaf.astype(jnp.float32), axes=1,
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.broadcast_to(g.astype(leaf.dtype)[None], leaf.shape)


def init_residuals(params):
    """Zero fp32 error-feedback residuals, one per pod-stacked leaf."""
    return jax.tree.map(
        lambda l: jnp.zeros(l.shape, jnp.float32), params
    )


def compress_pod_updates(
    leaf: jnp.ndarray, scheme: str, topk_frac: float = 0.05,
    residual: Optional[jnp.ndarray] = None,
):
    """Round-trip each pod's update through the wire compression.

    ``leaf`` is ``(n_pods, ...)``. Each pod's transmitted payload is its
    delta from the pod-0 reference; the returned array is what the
    aggregator reconstructs (``ref + decode(encode(delta))``), matching
    the decode-side view that ``repro.fl.compression.compress_delta``
    simulates on the host.

    With ``residual`` (fp32, same shape as ``leaf``), the residual is
    added to the delta before encoding and the call returns
    ``(decoded, new_residual)`` where ``new_residual = target -
    decode(encode(target))`` — per-pod error feedback. A ``"none"``
    scheme transmits exactly, so the residual passes through unchanged
    (as in the host pipeline).
    """
    scheme = check_scheme(scheme)
    if scheme == "none":
        return leaf if residual is None else (leaf, residual)
    ref = leaf[0]
    target = (leaf - ref[None]).astype(jnp.float32)
    if residual is not None:
        target = target + residual
    comp = target
    if "topk" in scheme:
        comp = jax.vmap(partial(topk_sparsify, frac=topk_frac))(comp)
    if "int8" in scheme:
        q, scale = jax.vmap(quantize_int8)(comp)
        comp = jax.vmap(dequantize_int8)(q, scale)
    decoded = (ref.astype(jnp.float32)[None] + comp).astype(leaf.dtype)
    if residual is None:
        return decoded
    return decoded, target - comp


def fedavg_pods(params, weights: jnp.ndarray, scheme: str = "none",
                topk_frac: float = 0.05, residuals=None):
    """Compressed weighted FedAvg over the pod axis of a param pytree.

    With ``residuals`` (a pytree from ``init_residuals``), applies
    error-feedback compression and returns ``(avg_params,
    new_residuals)``; without, returns ``avg_params`` (unchanged
    behaviour).  Each leaf's upload compression runs under the named
    scope ``fed.quantise`` and its cross-pod mean under
    ``fed.allreduce``.
    """
    w = weights.astype(jnp.float32)
    w_norm = w / jnp.sum(w)

    if residuals is None:
        def avg(leaf):
            with jax.named_scope("fed.quantise"):
                decoded = compress_pod_updates(leaf, scheme, topk_frac)
            with jax.named_scope("fed.allreduce"):
                return pod_weighted_mean(decoded, w_norm)

        return jax.tree.map(avg, params)

    def avg_ef(leaf, res):
        with jax.named_scope("fed.quantise"):
            decoded, new_res = compress_pod_updates(
                leaf, scheme, topk_frac, residual=res
            )
        with jax.named_scope("fed.allreduce"):
            return pod_weighted_mean(decoded, w_norm), new_res

    pairs = jax.tree.map(avg_ef, params, residuals)
    avg_params = jax.tree.map(
        lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    new_residuals = jax.tree.map(
        lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    return avg_params, new_residuals


# ---------------------------------------------------------------------------
# asynchronous (FedBuff) aggregation
# ---------------------------------------------------------------------------


def staleness_discount(staleness, power: float = 0.5) -> jnp.ndarray:
    """``(1 + τ)^-p`` — the FedBuff staleness weight (p=0.5 default)."""
    return (1.0 + jnp.asarray(staleness, jnp.float32)) ** (-power)


def compress_deltas(deltas: jnp.ndarray, scheme: str,
                    topk_frac: float = 0.05, residual=None):
    """Round-trip pod-stacked update *deltas* through the wire encoding.

    Unlike :func:`compress_pod_updates` there is no pod-0 reference —
    ``deltas`` already are the small wire payloads (params minus each
    pod's own download reference). With ``residual`` returns
    ``(decoded, new_residual)`` for error feedback; the caller masks
    the residual update to the pods that actually transmitted.
    """
    scheme = check_scheme(scheme)
    if scheme == "none":
        return deltas if residual is None else (deltas, residual)
    target = deltas.astype(jnp.float32)
    if residual is not None:
        target = target + residual
    comp = target
    if "topk" in scheme:
        comp = jax.vmap(partial(topk_sparsify, frac=topk_frac))(comp)
    if "int8" in scheme:
        q, scale = jax.vmap(quantize_int8)(comp)
        comp = jax.vmap(dequantize_int8)(q, scale)
    decoded = comp.astype(deltas.dtype)
    if residual is None:
        return decoded
    return decoded, target - comp


def _bmask(mask: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Reshape a ``(n_pods,)`` mask to broadcast over a stacked leaf."""
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


def fedbuff_pods(pending, global_params, weights: jnp.ndarray,
                 arrived: jnp.ndarray, staleness: jnp.ndarray,
                 server_lr: float = 1.0, scheme: str = "none",
                 topk_frac: float = 0.05, staleness_power: float = 0.5,
                 frac=None, residuals=None,
                 quorum_frac: Optional[float] = None,
                 n_expected=None):
    """Buffered staleness-weighted (FedBuff) merge over the pod axis.

    ``pending``: pytree of ``(n_pods, ...)`` snapshotted update deltas
    (each pod's upload payload); ``global_params``: pod-stacked
    broadcast copies of the current global model; ``arrived``:
    ``(n_pods,)`` bool — whose upload completed this round;
    ``staleness``: ``(n_pods,)`` rounds since each pod downloaded the
    model it trained on; ``frac``: optional served fraction in
    ``[0, 1]`` (partial updates). The new global is

        ``G' = G + server_lr · Σ_i (w_i/Σ_j w_j) · s_i · f_i · Δ_i``
        over arrived pods, ``s_i = (1+τ_i)^-p``, ``f_i`` the fraction

    (a no-op when nothing arrived). Data weights ``w`` mix the
    co-arrivals *relatively* (all fresh and complete ⇒ exactly the
    FedAvg delta step), while staleness and fraction discount each
    update *absolutely* — a lone stale or partial arrival moves the
    global by ``s·f·Δ``, never by the full delta (self-normalising the
    discounts would cancel them whenever one update arrives alone).
    Same fp32-accumulate/cast-back numerics as :func:`fedavg_pods`;
    with ``residuals`` the arrived pods' wire encodings run through
    error feedback (non-arrived pods' residuals pass through
    untouched) and the call returns ``(new_global, new_residuals)``.

    ``quorum_frac`` gates the merge in-graph (traceable — no host
    round-trip): fewer than ``ceil(quorum_frac * n_expected)`` arrivals
    (``n_expected`` defaults to ``n_pods``) zeroes every merge weight,
    so the round *degrades* — the global model passes through
    untouched, mirroring ``repro.fl.aggregation.quorum_commit``.
    """
    m = arrived.astype(jnp.float32)
    w = weights.astype(jnp.float32) * m
    s = staleness_discount(staleness, staleness_power)
    f = jnp.ones_like(w) if frac is None else jnp.asarray(frac, jnp.float32)
    # Σ w = 0 (no arrivals) must leave the global untouched
    w_norm = w / jnp.maximum(w.sum(), 1e-12) * s * f * m
    if quorum_frac is not None:
        n_exp = jnp.asarray(
            arrived.shape[0] if n_expected is None else n_expected,
            jnp.float32,
        )
        need = jnp.maximum(jnp.ceil(quorum_frac * n_exp), 1.0)
        w_norm = w_norm * (m.sum() >= need).astype(jnp.float32)

    def merge(leaf_delta, g, res=None):
        if res is None:
            decoded = compress_deltas(leaf_delta, scheme, topk_frac)
        else:
            decoded, cand = compress_deltas(
                leaf_delta, scheme, topk_frac, residual=res
            )
        upd = jnp.tensordot(w_norm, decoded.astype(jnp.float32), axes=1,
                            precision=jax.lax.Precision.HIGHEST)
        newg = (
            g.astype(jnp.float32) + server_lr * upd[None]
        ).astype(g.dtype)
        if res is None:
            return newg
        new_res = jnp.where(_bmask(arrived, res), cand, res)
        return newg, new_res

    if residuals is None:
        return jax.tree.map(merge, pending, global_params)
    pairs = jax.tree.map(merge, pending, global_params, residuals)
    new_global = jax.tree.map(
        lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    new_residuals = jax.tree.map(
        lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    return new_global, new_residuals
