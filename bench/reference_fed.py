"""Plain reference of the federated Mamba-2 training path: imports
nothing of ``src/``.

Everything here follows the published descriptions in straightforward
``jax.numpy``: the Mamba-2 block (arXiv:2405.21060, Sec. 7: in_proj to
``z | xBC | dt``, a causal depthwise conv and SiLU on ``xBC``, the SSM
with one group of ``B``/``C`` shared by the heads, a skip ``D``, the
gated RMSNorm ``norm(y * silu(z))`` and out_proj) in a pre-norm
residual stack with tied embeddings and cross-entropy loss; AdamW with
global-norm clipping (Loshchilov & Hutter, arXiv:1711.05101), with no
weight decay on norm scales and on ``A``, ``D`` and ``dt``, as Mamba-2
trains; and the int8 FedAvg round (arXiv:1602.05629 with symmetric
per-tensor int8 uploads of each pod's delta from pod 0).

The SSM is computed in its quadratic (attention-like) dual form,
``y = (L * C B^T) (dt x) + D x`` with ``L[t, s] = exp(sum_{s<k<=t}
dt_k A)`` built from stable segment sums, over the whole sequence: an
independent path from the program's chunked scan.  Departures of the
architecture from the published model, all as the program has them:
the conv has no bias, ``a_log`` and ``dt_bias`` start at zero, the
RMSNorm epsilon is 1e-6 everywhere.

Parameters are one pod's tree in the program's layout: ``embed``
``(V, D)``; ``units.b0.mix_norm.scale`` and ``units.b0.mixer.*``
stacked over layers on their leading axis (``in_proj`` ``(L, D,
2 d_in + 2 N + H)``, ``conv_w`` ``(L, K, d_in + 2 N)``, ``a_log``,
``dt_bias``, ``d_skip`` ``(L, H)``, ``gate_norm_scale`` ``(L, d_in)``,
``out_proj`` ``(L, d_in, D)``); ``final_norm.scale`` ``(D,)``.

Every matrix product goes through :func:`mm`: in float32 at the
highest precision (a TPU's default rounds float32 operands to
bfloat16), or, with ``low=True``, with its operands rounded to
bfloat16 and float32 accumulation, which is the correctness check's
control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6                    # RMSNorm epsilon
INT8_MAX = 127.0
NO_DECAY = ("a_log", "dt_bias", "d_skip", "gate_norm_scale", "scale")


def mm(spec: str, a, b, low: bool = False):
    """``einsum(spec, a, b)`` in float32 at the highest precision, or
    with bfloat16 operands when ``low``."""
    dt = jnp.bfloat16 if low else jnp.float32
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def causal_conv(x, w):
    """Depthwise causal conv over time: ``y[t] = sum_k w[k] x[t-K+1+k]``
    with zeros before the start. x: ``(b, S, C)``, w: ``(K, C)``."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[k] * xp[:, k:k + S] for k in range(K))


def segment_sums(a):
    """``out[..., t, s] = sum_{s<k<=t} a[..., k]`` for ``s <= t`` and
    ``-inf`` above the diagonal; ``a``: ``(..., S)``.  Summed directly,
    not as a difference of prefix sums."""
    S = a.shape[-1]
    strict = jnp.tril(jnp.ones((S, S), bool), -1)
    rep = jnp.where(strict, a[..., :, None], 0.0)     # [t, s] = a_t, t > s
    seg = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), seg, -jnp.inf)


def ssm(x, dt, A, B, C, D, low=False):
    """The SSM in its quadratic dual form. x ``(b, S, H, P)``, dt
    ``(b, S, H)`` (after softplus), A ``(H,)`` negative, B and C
    ``(b, S, N)``, D ``(H,)``; returns ``(b, S, H, P)``."""
    L = jnp.exp(segment_sums(jnp.moveaxis(dt * A, -1, 1)))   # (b, H, t, s)
    G = mm("btn,bsn->bts", C, B, low)
    M = G[:, None] * L
    y = mm("bhts,bshp->bthp", M, x * dt[..., None], low)
    return y + x * D[:, None]


def mamba2_block(p, x, low=False):
    """One residual Mamba-2 block; ``p`` is one layer's slice."""
    mix = p["mixer"]
    d_in = mix["gate_norm_scale"].shape[-1]
    H = mix["a_log"].shape[-1]
    N = (mix["conv_w"].shape[-1] - d_in) // 2
    h = rms_norm(x, p["mix_norm"]["scale"])
    proj = mm("bsd,de->bse", h, mix["in_proj"], low)
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * N], axis=-1)
    xbc = silu(causal_conv(xbc, mix["conv_w"]))
    xs, B, C = jnp.split(xbc, [d_in, d_in + N], axis=-1)
    b, S = x.shape[:2]
    y = ssm(xs.reshape(b, S, H, d_in // H), softplus(dt + mix["dt_bias"]),
            -jnp.exp(mix["a_log"]), B, C, mix["d_skip"], low)
    y = rms_norm(y.reshape(b, S, d_in) * silu(z), mix["gate_norm_scale"])
    return x + mm("bse,ed->bsd", y, mix["out_proj"], low)


def logits(params, tokens, low=False):
    """``(b, S, V)`` logits of token ids ``(b, S)``."""
    x = params["embed"][tokens]
    layers = params["units"]["b0"]
    for i in range(layers["mixer"]["a_log"].shape[0]):
        x = mamba2_block(jax.tree.map(lambda a: a[i], layers), x, low)
    x = rms_norm(x, params["final_norm"]["scale"])
    return mm("bsd,vd->bsv", x, params["embed"], low)


def loss_sum(params, tokens, labels, low=False):
    """Summed next-token cross-entropy over a block of sequences."""
    lg = logits(params, tokens, low)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@partial(jax.jit, static_argnames="low")
def _block_loss(params, tokens, labels, low):
    with jax.default_matmul_precision("highest"):
        return loss_sum(params, tokens, labels, low)


@partial(jax.jit, static_argnames="low")
def _block_grad(params, tokens, labels, low):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_sum)(params, tokens, labels, low)


def _blocks(n: int, block: int):
    block = max(1, min(block, n))
    if n % block:
        raise ValueError(f"{n} sequences do not split into blocks of {block}")
    return [slice(i, i + block) for i in range(0, n, block)]


def loss(params, tokens, labels, block: int = 4, low: bool = False):
    """Mean cross-entropy over ``(b, S)`` tokens, computed ``block``
    sequences at a time; a float."""
    tokens, labels = np.asarray(tokens), np.asarray(labels)
    total = sum(float(_block_loss(params, tokens[s], labels[s], low))
                for s in _blocks(len(tokens), block))
    return total / tokens.size


def loss_and_grads(params, tokens, labels, block: int = 4,
                   low: bool = False):
    """``(mean loss, its gradients)``, accumulated over blocks of
    ``block`` sequences in float32."""
    tokens, labels = np.asarray(tokens), np.asarray(labels)
    total, grads = 0.0, None
    for s in _blocks(len(tokens), block):
        val, g = _block_grad(params, tokens[s], labels[s], low)
        total += float(val)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


def decays(path) -> bool:
    """Whether AdamW decays the leaf at ``path`` (a key path)."""
    return getattr(path[-1], "key", None) not in NO_DECAY


@jax.jit
def adamw(params, grads, mu, nu, step, lr, hp):
    """One AdamW step after global-norm clipping: ``(params, mu, nu)``
    at step count ``step`` (1 for the first), learning rate ``lr``;
    ``hp`` holds ``b1``, ``b2``, ``eps``, ``weight_decay``,
    ``grad_clip``."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["grad_clip"] / (norm + 1e-9))
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g * clip, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * (g * clip) ** 2,
                      nu, grads)
    t = jnp.asarray(step, jnp.float32)

    def update(path, p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"])
        if decays(path):
            u = u + hp["weight_decay"] * p
        return p - lr * u

    return jax.tree_util.tree_map_with_path(update, params, mu, nu), mu, nu


def int8_roundtrip(delta):
    """A tensor as the aggregator decodes it after symmetric per-tensor
    int8: scale ``max|delta| / 127`` (1 for an all-zero tensor), values
    rounded half to even and clipped to ``[-127, 127]``."""
    amax = jnp.max(jnp.abs(delta))
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
    return jnp.clip(jnp.round(delta / scale), -INT8_MAX, INT8_MAX) * scale


@partial(jax.jit, static_argnames="low")
def fedavg_int8(pods, weights, low: bool = False):
    """The int8 FedAvg round: the ``weights``-weighted mean of every
    pod's params as uploaded, each pod sending its delta from pod 0 in
    int8.  ``pods`` is a list of param trees; returns one tree."""
    w = weights / jnp.sum(weights)

    def leaf(*xs):
        ref = xs[0]
        decoded = jnp.stack([ref + int8_roundtrip(x - ref) for x in xs])
        if low:
            return mm("p,p...->...", w, decoded, low=True)
        return sum(w[q] * decoded[q] for q in range(len(xs)))

    return jax.tree.map(leaf, *pods)



def warmup_cosine(count: int, peak: float, warmup: int, total: int,
                  final_frac: float = 0.1) -> float:
    """The learning rate of the step taken after ``count`` steps: linear
    from 0 to ``peak`` over ``warmup`` steps, then a cosine down to
    ``final_frac * peak`` at ``total``."""
    if count < warmup:
        return peak * count / max(warmup, 1)
    progress = min(max((count - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (final_frac
                   + (1 - final_frac) * 0.5 * (1 + np.cos(np.pi * progress)))


def train_flops_per_token(d_model: int, n_layers: int, vocab: int,
                          d_state: int, expand: int, head_dim: int,
                          d_conv: int, chunk: int, seq_len: int) -> float:
    """Matrix-multiply operations one training token needs, forward and
    backward (3x forward), with no recomputation: per layer the in and
    out projections, the causal conv and the chunked SSD's four
    contractions (``C B^T`` and its product with ``x`` within a chunk
    of ``Q = min(chunk, seq_len)``, the chunk states and their read-out),
    plus the tied output head."""
    d_in = expand * d_model
    H = d_in // head_dim
    N, Q = d_state, min(chunk, seq_len)
    proj = 2 * d_model * (2 * d_in + 2 * N + H) + 2 * d_in * d_model
    conv = 2 * d_conv * (d_in + 2 * N)
    scan = 2 * Q * N + 2 * Q * H * head_dim + 4 * N * H * head_dim
    forward = n_layers * (proj + conv + scan) + 2 * d_model * vocab
    return 3.0 * forward
