"""Per-layer block assembly: one (mixer + FFN) residual block per kind.

Blocks receive the residual-stream input and return the *new* stream (plus
an MoE aux-loss contribution and, in prefill/decode modes, the layer cache).
Sequence-parallel constraints on the residual stream are applied here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import recurrent as rec
from repro.models import xlstm
from repro.models.common import mlp_forward, mlp_init, rms_norm
from repro.models.moe import moe_ffn, moe_init


def _window(cfg: ModelConfig, kind: str) -> int:
    return cfg.window if kind == "win" else 0


def block_init(key, cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    pd = cfg.pdtype()
    k1, k2 = jax.random.split(key)
    p = {"norm1": jnp.zeros((d,), pd)}
    if kind in ("attn", "win", "moe"):
        p["attn"] = attn.attn_init(k1, cfg)
        p["norm2"] = jnp.zeros((d,), pd)
        if kind == "moe":
            p["moe"] = moe_init(k2, cfg)
        else:
            p["mlp"] = mlp_init(k2, d, cfg.d_ff, cfg.mlp_kind, pd)
    elif kind == "rec":
        p["rec"] = rec.rglru_init(k1, cfg)
        p["norm2"] = jnp.zeros((d,), pd)
        p["mlp"] = mlp_init(k2, d, cfg.d_ff, cfg.mlp_kind, pd)
    elif kind == "mlstm":
        p["cell"] = xlstm.mlstm_init(k1, cfg)
    elif kind == "slstm":
        p["cell"] = xlstm.slstm_init(k1, cfg)
    else:
        raise ValueError(kind)
    return p


def _res(x):
    return constrain(x, "dp", "seq", None)


def residual_add(x, y, cfg: ModelConfig):
    """``x + residual_multiplier * y``: one sublayer's output joining the
    residual stream (plain ``x + y`` at the identity default)."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def block_train(x, params, cfg: ModelConfig, kind: str):
    """[B,S,D] -> ([B,S,D], aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ("attn", "win", "moe"):
        y = attn.attn_train(h, params["attn"], cfg, _window(cfg, kind))
        x = _res(residual_add(x, y, cfg))
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind == "moe":
            y, aux = moe_ffn(h2, params["moe"], cfg)
        else:
            y = mlp_forward(h2, params["mlp"], cfg.mlp_kind)
        x = _res(residual_add(x, y, cfg))
    elif kind == "rec":
        x = _res(residual_add(x, rec.rec_block_train(h, params["rec"], cfg), cfg))
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = _res(residual_add(x, mlp_forward(h2, params["mlp"], cfg.mlp_kind), cfg))
    elif kind == "mlstm":
        y = xlstm.mlstm_block(h, params["cell"], cfg, mode="train")
        x = _res(residual_add(x, y, cfg))
    elif kind == "slstm":
        y = xlstm.slstm_block(h, params["cell"], cfg, mode="train")
        x = _res(residual_add(x, y, cfg))
    else:
        raise ValueError(kind)
    return x, aux


def block_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in ("attn", "moe"):
        return attn.init_kv_cache(cfg, batch, max_len)
    if kind == "win":
        return attn.init_kv_cache(cfg, batch, max_len, cfg.window)
    if kind == "rec":
        return rec.init_rec_cache(cfg, batch)
    if kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return xlstm.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def block_prefill(x, params, cfg: ModelConfig, kind: str):
    """[B,S,D] -> (x', cache) building the decode cache as it goes."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ("attn", "win", "moe"):
        y, cache = attn.attn_prefill(h, params["attn"], cfg, _window(cfg, kind))
        x = _res(residual_add(x, y, cfg))
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind == "moe":
            y2, _ = moe_ffn(h2, params["moe"], cfg)
        else:
            y2 = mlp_forward(h2, params["mlp"], cfg.mlp_kind)
        x = _res(residual_add(x, y2, cfg))
    elif kind == "rec":
        y, cache = rec.rec_block_prefill(h, params["rec"], cfg)
        x = _res(residual_add(x, y, cfg))
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = _res(residual_add(x, mlp_forward(h2, params["mlp"], cfg.mlp_kind), cfg))
    elif kind == "mlstm":
        y, cache = xlstm.mlstm_block(h, params["cell"], cfg, mode="prefill")
        x = _res(residual_add(x, y, cfg))
    elif kind == "slstm":
        y, cache = xlstm.slstm_block(h, params["cell"], cfg, mode="prefill")
        x = _res(residual_add(x, y, cfg))
    else:
        raise ValueError(kind)
    return x, cache


def block_decode(x, params, cfg: ModelConfig, kind: str, cache, pos):
    """[B,1,D] -> (x', cache')."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ("attn", "win", "moe"):
        y, cache = attn.attn_decode(
            h, params["attn"], cfg, cache, pos, _window(cfg, kind)
        )
        x = residual_add(x, y, cfg)
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind == "moe":
            y2, _ = moe_ffn(h2, params["moe"], cfg)
        else:
            y2 = mlp_forward(h2, params["mlp"], cfg.mlp_kind)
        x = residual_add(x, y2, cfg)
    elif kind == "rec":
        y, cache = rec.rec_block_decode(h, params["rec"], cfg, cache)
        x = residual_add(x, y, cfg)
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = residual_add(x, mlp_forward(h2, params["mlp"], cfg.mlp_kind), cfg)
    elif kind == "mlstm":
        y, cache = xlstm.mlstm_block(h, params["cell"], cfg, cache, mode="decode")
        x = residual_add(x, y, cfg)
    elif kind == "slstm":
        y, cache = xlstm.slstm_block(h, params["cell"], cfg, cache, mode="decode")
        x = residual_add(x, y, cfg)
    else:
        raise ValueError(kind)
    return x, cache
