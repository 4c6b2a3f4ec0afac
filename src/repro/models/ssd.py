"""Mamba-2 mixer built on SSD (state-space duality) — arXiv:2405.21060.

Block: in_proj -> [z | xBC | dt] -> causal conv on xBC -> SiLU ->
SSD recurrence over heads -> gated RMSNorm(y * silu(z)) -> out_proj.

The model path uses the *chunked* SSD algorithm in pure jnp (linear in S,
matmul-dominated — the TPU-native adaptation: intra-chunk quadratic term hits
the MXU, inter-chunk low-rank state pass is a cheap scan). The Pallas kernel
(repro.kernels.ssd) mirrors the same schedule with explicit VMEM tiling and
``ref.py`` holds the slow token-recurrence oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init
from repro.models.rglru import causal_conv1d

NEG_INF = -1e30


def ssd_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.d_head
    d_xbc = d_inner + 2 * s.d_state
    return d_inner, n_heads, d_xbc


def ssd_block_init(key, cfg: ModelConfig):
    s = cfg.ssm
    D = cfg.d_model
    d_inner, n_heads, d_xbc = ssd_dims(cfg)
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    d_proj = d_inner + d_xbc + n_heads  # z | xBC | dt
    return {
        "in_proj": dense_init(ks[0], D, d_proj, dt),
        "conv_w": (
            jax.random.normal(ks[1], (s.d_conv, d_xbc)) * (s.d_conv ** -0.5)
        ).astype(dt),
        "a_log": jnp.zeros((n_heads,), dt),         # A = -exp(a_log) = -1
        "dt_bias": jnp.zeros((n_heads,), dt),
        "d_skip": jnp.ones((n_heads,), dt),
        "gate_norm_scale": jnp.ones((d_inner,), dt),
        "out_proj": dense_init(ks[2], d_inner, D, dt),
    }


def _split_proj(params, x, cfg: ModelConfig):
    d_inner, n_heads, d_xbc = ssd_dims(cfg)
    proj = x @ params["in_proj"].astype(x.dtype)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner : d_inner + d_xbc]
    dt_raw = proj[..., d_inner + d_xbc :]
    return z, xbc, dt_raw


def _conv_split(params, xbc, cfg: ModelConfig, conv_state=None):
    s = cfg.ssm
    d_inner, _, _ = ssd_dims(cfg)
    xbc, new_conv = causal_conv1d(xbc, params["conv_w"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_inner]
    B_mat = xbc[..., d_inner : d_inner + s.d_state]
    C_mat = xbc[..., d_inner + s.d_state :]
    return xs, B_mat, C_mat, new_conv


def ssd_chunked(xh, B_mat, C_mat, dt, a, chunk, h0=None):
    """Chunked SSD scan.

    xh:    (B, S, H, P)   per-head inputs
    B_mat: (B, S, N)      input projection (single group, shared across heads)
    C_mat: (B, S, N)      output projection
    dt:    (B, S, H)      positive step sizes (post-softplus) fp32
    a:     (H,)           negative decay rates (A = -exp(a_log)) fp32
    h0:    (B, H, P, N)   initial state or None
    Returns (y: (B,S,H,P), h_final: (B,H,P,N)) in fp32.
    """
    Bsz, S, H, P = xh.shape
    N = B_mat.shape[-1]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # pad tail with dt=0 steps: decay exp(0)=1 keeps state, zero input
        pad = Q - S % Q
        pad_cfg = [(0, 0), (0, pad)] + [(0, 0)] * (xh.ndim - 2)
        xh = jnp.pad(xh, pad_cfg)
        B_mat = jnp.pad(B_mat, [(0, 0), (0, pad), (0, 0)])
        C_mat = jnp.pad(C_mat, [(0, 0), (0, pad), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
        S = S + pad
    nc = S // Q

    xh = xh.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    Bm = B_mat.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cm = C_mat.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    dt = dt.reshape(Bsz, nc, Q, H)

    dA = dt * a[None, None, None, :]                     # (B,nc,Q,H) negative
    cum = jnp.cumsum(dA, axis=2)                         # inclusive cumsum
    seg_total = cum[:, :, -1:, :]                        # (B,nc,1,H)

    # --- intra-chunk (quadratic in Q, matmul-dominated) ---
    # L[t, s] = exp(cum_t - cum_s) for s <= t else 0
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    # mask before the exp: above the diagonal rel > 0, and at Q=128 with
    # dt ~ softplus(0) it exceeds float32's exp range (inf * 0 = NaN)
    L = jnp.exp(jnp.where(tri, rel, -jnp.inf))
    cb = jnp.einsum("bcqn,bcsn->bcqs", Cm, Bm)           # (B,nc,Q,Q)
    scores = cb[..., None] * L                           # (B,nc,Q,Q,H)
    xdt = xh * dt[..., None]                             # (B,nc,Q,H,P)
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", scores, xdt)

    # --- per-chunk end state: sum_s exp(seg_total - cum_s) dt_s B_s x_s ---
    decay_to_end = jnp.exp(seg_total - cum)              # (B,nc,Q,H)
    states = jnp.einsum(
        "bcqh,bcqn,bcqhp->bchpn", decay_to_end, Bm, xdt
    )                                                    # (B,nc,H,P,N)

    # --- inter-chunk recurrence over nc (cheap scan) ---
    seg_decay = jnp.exp(seg_total[:, :, 0, :])           # (B,nc,H)

    def step(h, inp):
        sd, st = inp                                     # (B,H), (B,H,P,N)
        h_new = h * sd[..., None, None] + st
        return h_new, h                                  # emit state *before*

    h_init = (
        jnp.zeros((Bsz, H, P, N), jnp.float32) if h0 is None
        else h0.astype(jnp.float32)
    )
    h_last, h_prev = jax.lax.scan(
        step,
        h_init,
        (jnp.moveaxis(seg_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    h_prev = jnp.moveaxis(h_prev, 0, 1)                  # (B,nc,H,P,N)

    # --- inter-chunk contribution: C_t exp(cum_t) h_prev ---
    y_inter = jnp.einsum(
        "bcqn,bchpn,bcqh->bcqhp", Cm, h_prev, jnp.exp(cum)
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)[:, :S_orig]
    return y, h_last


def _gated_norm(y, z, scale, eps: float = 1e-6):
    g = y * jax.nn.silu(z.astype(y.dtype))
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return g * jax.lax.rsqrt(ms + eps) * scale.astype(y.dtype)


def _ssd_core(params, x, cfg, conv_state=None, h0=None):
    s = cfg.ssm
    d_inner, n_heads, _ = ssd_dims(cfg)
    Bsz, S, _ = x.shape
    z, xbc, dt_raw = _split_proj(params, x, cfg)
    xs, B_mat, C_mat, new_conv = _conv_split(params, xbc, cfg, conv_state)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xs.reshape(Bsz, S, n_heads, s.d_head)
    y, h_last = ssd_chunked(xh, B_mat, C_mat, dt, a, s.chunk, h0)
    y = y + xh.astype(jnp.float32) * params["d_skip"].astype(jnp.float32)[
        None, None, :, None
    ]
    y = y.reshape(Bsz, S, d_inner).astype(x.dtype)
    y = _gated_norm(y, z, params["gate_norm_scale"])
    return y @ params["out_proj"].astype(x.dtype), new_conv, h_last


def ssd_full(params, x, cfg: ModelConfig, spec=None, positions=None):
    y, _, _ = _ssd_core(params, x, cfg)
    return y


def init_ssd_cache(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_inner, n_heads, d_xbc = ssd_dims(cfg)
    return {
        "h": jnp.zeros((batch, n_heads, s.d_head, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, d_xbc), cfg.dtype),
    }


def ssd_prefill(params, x, cfg, spec, positions, cache):
    y, new_conv, h_last = _ssd_core(params, x, cfg, cache["conv"], cache["h"])
    return y, {"h": h_last, "conv": new_conv}


def ssd_decode(params, x, cfg, spec, pos, cache):
    """Single-token state update. x: (B,1,D)."""
    s = cfg.ssm
    d_inner, n_heads, _ = ssd_dims(cfg)
    Bsz = x.shape[0]
    z, xbc, dt_raw = _split_proj(params, x, cfg)
    xs, B_mat, C_mat, new_conv = _conv_split(params, xbc, cfg, cache["conv"])
    dt = jax.nn.softplus(
        dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )                                                    # (B,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xs[:, 0].reshape(Bsz, n_heads, s.d_head).astype(jnp.float32)
    dA = jnp.exp(dt * a[None, :])                        # (B,H)
    inc = jnp.einsum(
        "bh,bn,bhp->bhpn", dt, B_mat[:, 0].astype(jnp.float32), xh
    )
    h = cache["h"] * dA[..., None, None] + inc
    y = jnp.einsum("bn,bhpn->bhp", C_mat[:, 0].astype(jnp.float32), h)
    y = y + xh * params["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(Bsz, 1, d_inner).astype(x.dtype)
    y = _gated_norm(y, z, params["gate_norm_scale"])
    return y @ params["out_proj"].astype(x.dtype), {"h": h, "conv": new_conv}
