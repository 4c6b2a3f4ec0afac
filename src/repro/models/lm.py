"""Decoder-only language model assembled from the config-driven block zoo.

Layer stacking: the config's repeating *pattern unit* (e.g. gemma3's
5×local + 1×global, recurrentgemma's rec-rec-attn) is initialised as a
stacked pytree with a leading ``n_units`` axis and applied with
``jax.lax.scan`` — HLO size is O(1) in depth, which is what a production
deployment (and a 1-core compile budget) needs. Remainder layers
(n_layers % unit_len) get their own unrolled params.

Three entry points per the assigned shapes:
  ``forward_train`` (+ ``loss_fn``)  — train_4k
  ``prefill``                        — prefill_32k (fills KV caches)
  ``decode_step``                    — decode_32k / long_500k (1 token)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, RGLRU, SSD, LayerSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (
    apply_norm,
    embed_init,
    mlp_apply,
    mlp_init,
    norm_init,
    sinusoidal_embed,
    softmax_cross_entropy,
)


def _add_abs_pos(x, cfg, positions):
    if cfg.abs_sinusoidal:
        x = x + sinusoidal_embed(positions, cfg.d_model).astype(x.dtype)
    return x

# ---------------------------------------------------------------------------
# block = mixer (+ FFN/MoE) with pre-norms
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig, spec: LayerSpec):
    k_mix, k_ffn, k_moe = jax.random.split(key, 3)
    p = {"mix_norm": norm_init(cfg)}
    if spec.kind == ATTN:
        p["mixer"] = attn_mod.attn_init(k_mix, cfg)
    elif spec.kind == RGLRU:
        p["mixer"] = rglru_mod.rglru_block_init(k_mix, cfg)
    elif spec.kind == SSD:
        p["mixer"] = ssd_mod.ssd_block_init(k_mix, cfg)
    if spec.kind != SSD:  # mamba2 blocks carry no FFN (d_ff == 0)
        if cfg.moe is not None and spec.kind == ATTN:
            p["ffn_norm"] = norm_init(cfg)
            p["moe"] = moe_mod.moe_init(k_moe, cfg)
            if cfg.moe.dense_residual:
                p["mlp"] = mlp_init(k_ffn, cfg)          # arctic dense branch
        elif cfg.d_ff > 0:
            p["ffn_norm"] = norm_init(cfg)
            p["mlp"] = mlp_init(k_ffn, cfg)
    return p


def _block_apply(params, x, cfg, spec, positions, mode, cache, pos):
    """Returns (y, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(params["mix_norm"], x, cfg)
    new_cache = cache
    if spec.kind == ATTN:
        if mode == "train":
            mix = attn_mod.attn_full(params["mixer"], h, cfg, spec, positions)
        elif mode == "prefill":
            mix, new_cache = attn_mod.attn_prefill(
                params["mixer"], h, cfg, spec, positions, cache
            )
        else:
            mix, new_cache = attn_mod.attn_decode(
                params["mixer"], h, cfg, spec, pos, cache
            )
    elif spec.kind == RGLRU:
        if mode == "train":
            mix = rglru_mod.rglru_full(params["mixer"], h, cfg, spec, positions)
        elif mode == "prefill":
            mix, new_cache = rglru_mod.rglru_prefill(
                params["mixer"], h, cfg, spec, positions, cache
            )
        else:
            mix, new_cache = rglru_mod.rglru_decode(
                params["mixer"], h, cfg, spec, pos, cache
            )
    else:  # SSD
        if mode == "train":
            mix = ssd_mod.ssd_full(params["mixer"], h, cfg, spec, positions)
        elif mode == "prefill":
            mix, new_cache = ssd_mod.ssd_prefill(
                params["mixer"], h, cfg, spec, positions, cache
            )
        else:
            mix, new_cache = ssd_mod.ssd_decode(
                params["mixer"], h, cfg, spec, pos, cache
            )
    x = x + mix

    if "moe" in params:
        h2 = apply_norm(params["ffn_norm"], x, cfg)
        moe_out, moe_aux = moe_mod.moe_apply(params["moe"], h2, cfg)
        aux = aux + moe_aux
        ffn_out = moe_out
        if "mlp" in params:                              # arctic dense residual
            ffn_out = ffn_out + mlp_apply(params["mlp"], h2, cfg)
        x = x + ffn_out
    elif "mlp" in params:
        h2 = apply_norm(params["ffn_norm"], x, cfg)
        x = x + mlp_apply(params["mlp"], h2, cfg)
    return x, new_cache, aux


def _unit_init(key, cfg: ModelConfig, pattern):
    keys = jax.random.split(key, max(len(pattern), 1))
    return {
        f"b{i}": _block_init(keys[i], cfg, spec)
        for i, spec in enumerate(pattern)
    }


def _unit_apply(params, x, cfg, pattern, positions, mode, cache, pos):
    new_cache = {}
    aux = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(pattern):
        c = cache.get(f"b{i}") if cache else None
        x, nc, a = _block_apply(
            params[f"b{i}"], x, cfg, spec, positions, mode, c, pos
        )
        if nc is not None:
            new_cache[f"b{i}"] = nc
        aux = aux + a
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    k_emb, k_units, k_rem, k_head = jax.random.split(key, 4)
    unit_keys = jax.random.split(k_units, cfg.n_units)
    params = {
        "embed": embed_init(k_emb, cfg.vocab_size, cfg.d_model, cfg.param_dtype),
        "units": jax.vmap(lambda k: _unit_init(k, cfg, cfg.pattern))(unit_keys),
        "final_norm": norm_init(cfg),
    }
    if cfg.n_remainder:
        params["rem"] = _unit_init(k_rem, cfg, cfg.remainder_pattern)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * 0.02
        ).astype(cfg.param_dtype)
    return params


def weight_decay_mask(params):
    """The leaves AdamW decays: the matrices (rank 2 or more per layer).
    A leaf under ``units`` is stacked over the pattern units on its
    leading axis, so that axis does not count; norm scales, biases and
    the SSD's per-head ``a_log``, ``dt_bias`` and ``d_skip`` are not
    decayed (arXiv:2405.21060 leaves A, D and dt out of weight decay)."""
    def mask(path, leaf):
        return leaf.ndim - (path[0].key == "units") >= 2

    return jax.tree_util.tree_map_with_path(mask, params)


def _embed(params, cfg, tokens, extra_embeds):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cfg.dtype), x], axis=1)
    return x


def _logits(params, cfg, x):
    x = apply_norm(params["final_norm"], x, cfg)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cfg.dtype)
    logits = x @ head
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return fn


def _run_stack(params, cfg, x, positions, mode, cache, pos):
    """Scan the stacked units, then the remainder unit."""
    aux0 = jnp.zeros((), jnp.float32)

    def unit_fn(carry, xs):
        xc, aux = carry
        unit_params, unit_cache = xs
        y, new_cache, a = _unit_apply(
            unit_params, xc, cfg, cfg.pattern, positions, mode, unit_cache, pos
        )
        return (y, aux + a), new_cache

    unit_fn = _remat_wrap(unit_fn, cfg)
    stacked_cache = cache["units"] if cache else None
    if stacked_cache is None:

        def unit_fn_nocache(carry, unit_params):  # train path, no cache
            xc, aux = carry
            y, _, a = _unit_apply(
                unit_params, xc, cfg, cfg.pattern, positions, mode, None, pos
            )
            return (y, aux + a), None

        unit_fn_nocache = _remat_wrap(unit_fn_nocache, cfg)
        (x, aux), _ = jax.lax.scan(
            unit_fn_nocache, (x, aux0), params["units"]
        )
        new_unit_caches = None
    else:
        (x, aux), new_unit_caches = jax.lax.scan(
            unit_fn, (x, aux0), (params["units"], stacked_cache)
        )

    new_rem_cache = None
    if cfg.n_remainder:
        rem_cache = cache["rem"] if cache else None
        x, new_rem_cache, a = _unit_apply(
            params["rem"], x, cfg, cfg.remainder_pattern, positions, mode,
            rem_cache, pos,
        )
        aux = aux + a
    new_cache = None
    if cache is not None:
        new_cache = {"units": new_unit_caches, "rem": new_rem_cache,
                     "pos": cache["pos"] + (1 if mode == "decode" else 0)}
        if mode == "prefill":
            new_cache["pos"] = jnp.asarray(positions.shape[-1], jnp.int32)
        if new_rem_cache is None:
            new_cache.pop("rem")
    return x, new_cache, aux


def forward_train(params, cfg: ModelConfig, tokens, extra_embeds=None):
    """tokens: (B, S_text) int32; extra_embeds: (B, n_frontend, D) or None."""
    x = _embed(params, cfg, tokens, extra_embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _add_abs_pos(x, cfg, positions)
    x, _, aux = _run_stack(params, cfg, x, positions, "train", None, None)
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch):
    """batch: tokens (B,S), labels (B,S), optional weights, extra_embeds."""
    logits, aux = forward_train(
        params, cfg, batch["tokens"], batch.get("extra_embeds")
    )
    n_front = cfg.n_frontend_tokens if batch.get("extra_embeds") is not None else 0
    logits = logits[:, n_front:]
    loss = softmax_cross_entropy(logits, batch["labels"], batch.get("weights"))
    return loss + aux


# ---------------------------------------------------------------------------
# caches / serving
# ---------------------------------------------------------------------------


def _block_cache(cfg, spec: LayerSpec, batch, max_len):
    if spec.kind == ATTN:
        return attn_mod.init_layer_cache(cfg, spec, batch, max_len)
    if spec.kind == RGLRU:
        return rglru_mod.init_rglru_cache(cfg, batch)
    return ssd_mod.init_ssd_cache(cfg, batch)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    unit_cache = {
        f"b{i}": _block_cache(cfg, spec, batch, max_len)
        for i, spec in enumerate(cfg.pattern)
    }
    cache = {
        "units": jax.tree.map(
            lambda l: jnp.zeros((cfg.n_units,) + l.shape, l.dtype), unit_cache
        ),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.n_remainder:
        cache["rem"] = {
            f"b{i}": _block_cache(cfg, spec, batch, max_len)
            for i, spec in enumerate(cfg.remainder_pattern)
        }
    return cache


def prefill(params, cfg: ModelConfig, tokens, cache, extra_embeds=None):
    """Forward over the prompt, filling caches. Returns (logits, cache)."""
    x = _embed(params, cfg, tokens, extra_embeds)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _add_abs_pos(x, cfg, positions)
    x, new_cache, _ = _run_stack(params, cfg, x, positions, "prefill", cache, None)
    return _logits(params, cfg, x[:, -1:]), new_cache


def decode_step(params, cfg: ModelConfig, token, cache):
    """token: (B, 1) int32. Returns (logits (B,1,V), new_cache)."""
    pos = cache["pos"]
    x = _embed(params, cfg, token, None)
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    x = _add_abs_pos(x, cfg, positions)
    x, new_cache, _ = _run_stack(params, cfg, x, positions, "decode", cache, pos)
    return _logits(params, cfg, x), new_cache
