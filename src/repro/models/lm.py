"""Unified causal LM over heterogeneous block stacks.

The layer stack is ``layer_pattern × repeats + tail_pattern``.  All repeats
of the period are stacked on a leading axis and executed with
``lax.scan`` (small HLO even at 96 layers), each period wrapped in
``jax.checkpoint`` for training.  Three entry points:

  ``train_loss``   tokens/embeds + labels -> scalar loss
  ``prefill``      tokens/embeds -> (last-position logits, decode cache)
  ``decode_step``  one token + cache + pos -> (logits, new cache)

Modality-frontend stubs (musicgen/llava): ``embed_inputs=False`` makes the
input a precomputed embedding tensor ``[B, S, d_model]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import blocks as B
from repro.models.common import embed_init, dense_init, rms_norm, softcap


# -- parameters ---------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 4 + cfg.n_layers)
    params: dict[str, Any] = {}
    if cfg.embed_inputs:
        params["embed"] = embed_init(keys[0], (cfg.vocab_size, cfg.d_model), cfg.pdtype())
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), cfg.pdtype()
        )
    elif not cfg.embed_inputs:
        # stub-frontend models cannot tie (no input table); always have a head
        params["lm_head"] = dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), cfg.pdtype()
        )
    params["final_norm"] = jnp.zeros((cfg.d_model,), cfg.pdtype())

    period = cfg.layer_pattern
    kidx = 2
    stacked = []
    for pos, kind in enumerate(period):
        # repeat r of position pos takes key kidx + r * len(period) + pos;
        # vmap builds the stacked leaves directly (no per-layer copies)
        layer_keys = keys[kidx + pos : kidx + cfg.repeats * len(period) : len(period)]
        stacked.append(jax.vmap(lambda k, kind=kind: B.block_init(k, cfg, kind))(layer_keys))
    params["period"] = stacked
    kidx += cfg.repeats * len(period)
    params["tail"] = [
        B.block_init(keys[kidx + i], cfg, kind)
        for i, kind in enumerate(cfg.tail_pattern)
    ]
    return params


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic parameter count via shape evaluation (exact)."""
    import numpy as np

    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = int(np.prod(leaf.shape, dtype=np.int64))
        if active_only and cfg.moe is not None:
            names = [getattr(p, "key", None) for p in path]
            if any(n_ in ("e_gate", "e_in", "e_out") for n_ in names):
                n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n
    return total


# -- embedding / head ------------------------------------------------------------


def embed_tokens(params, inputs, cfg: ModelConfig):
    if cfg.embed_inputs:
        x = jnp.take(params["embed"], inputs, axis=0).astype(cfg.dtype())
    else:
        x = inputs.astype(cfg.dtype())  # frontend stub: already embeddings
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype())
    if cfg.embed_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embed_multiplier, cfg.dtype())
    return constrain(x, "dp", "seq", None)


def lm_logits(params, x, cfg: ModelConfig):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    logits = softcap(logits, cfg.final_softcap)
    return constrain(logits, "dp", None, "tp")


# -- stacks ---------------------------------------------------------------------


def _run_train_stack(x, params, cfg: ModelConfig):
    period = cfg.layer_pattern

    def period_body(carry, stacked):
        x, aux = carry
        for i, kind in enumerate(period):
            x, a = B.block_train(x, stacked[i], cfg, kind)
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(
        period_body, policy=jax.checkpoint_policies.nothing_saveable
    )
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["period"])
    for i, kind in enumerate(cfg.tail_pattern):
        x, a = B.block_train(x, params["tail"][i], cfg, kind)
        aux = aux + a
    return x, aux


def train_loss(params, batch: dict, cfg: ModelConfig):
    """batch: {"inputs": [B,S] int32 (or [B,S,D] embeds), "labels": [B,S] int32}.

    Returns (loss, metrics dict).  Label -100 positions are masked.
    """
    x = embed_tokens(params, batch["inputs"], cfg)
    x, aux = _run_train_stack(x, params, cfg)
    logits = lm_logits(params, x, cfg)  # [B,S,V] fp32
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux / max(cfg.n_layers, 1)
    return loss, {"nll": loss, "tokens": denom}


# -- cache ------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    period_caches = []
    for pos, kind in enumerate(cfg.layer_pattern):
        one = B.block_cache_init(cfg, kind, batch, max_len)
        stacked = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (cfg.repeats,) + l.shape), one
        )
        period_caches.append(stacked)
    tail = [
        B.block_cache_init(cfg, kind, batch, max_len) for kind in cfg.tail_pattern
    ]
    return {"period": period_caches, "tail": tail}


def prefill(params, inputs, cfg: ModelConfig, max_len: int):
    """Process a prompt; returns (last-token logits [B,V], cache at pos=S)."""
    x = embed_tokens(params, inputs, cfg)
    period = cfg.layer_pattern

    def period_body(x, stacked_params):
        caches = []
        for i, kind in enumerate(period):
            x, c = B.block_prefill(x, stacked_params[i], cfg, kind)
            caches.append(c)
        return x, caches

    x, period_cache = lax.scan(period_body, x, params["period"])
    tail_cache = []
    for i, kind in enumerate(cfg.tail_pattern):
        x, c = B.block_prefill(x, params["tail"][i], cfg, kind)
        tail_cache.append(c)
    logits = lm_logits(params, x[:, -1:], cfg)[:, 0]
    cache = {"period": period_cache, "tail": tail_cache}
    cache = _grow_kv(cache, cfg, max_len)
    return logits, cache


def _grow_kv(cache, cfg: ModelConfig, max_len: int):
    """Pad prefill KV caches (length S) out to max_len slots for decode."""

    def grow(x):
        return x

    period = []
    for pos, kind in enumerate(cfg.layer_pattern):
        c = cache["period"][pos]
        if kind in ("attn", "moe"):
            pad = max_len - c["k"].shape[2]
            if pad > 0:
                c = {
                    k: jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                    for k, v in c.items()
                }
        period.append(c)
    tail = []
    for i, kind in enumerate(cfg.tail_pattern):
        c = cache["tail"][i]
        if kind in ("attn", "moe"):
            pad = max_len - c["k"].shape[1]
            if pad > 0:
                c = {
                    k: jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    for k, v in c.items()
                }
        tail.append(c)
    return {"period": period, "tail": tail}


def decode_step(params, cache, inputs, pos, cfg: ModelConfig):
    """One token for every sequence.  inputs: [B,1] ids (or [B,1,D] embeds);
    pos: scalar int32 count of already-cached tokens.  Returns (logits [B,V],
    new cache)."""
    x = embed_tokens(params, inputs, cfg)
    period = cfg.layer_pattern

    def period_body(x, layer):
        stacked_params, stacked_cache = layer
        new_caches = []
        for i, kind in enumerate(period):
            x, c = B.block_decode(x, stacked_params[i], cfg, kind, stacked_cache[i], pos)
            new_caches.append(c)
        return x, new_caches

    x, new_period = lax.scan(period_body, x, (params["period"], cache["period"]))
    new_tail = []
    for i, kind in enumerate(cfg.tail_pattern):
        x, c = B.block_decode(x, params["tail"][i], cfg, kind, cache["tail"][i], pos)
        new_tail.append(c)
    logits = lm_logits(params, x, cfg)[:, 0]
    return logits, {"period": new_period, "tail": new_tail}
